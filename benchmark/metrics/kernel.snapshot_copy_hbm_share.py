"""Bytes the traced window's snapshot stores and seeds must move (a seed: an
entry's state leaves, keys and values read and written into a scratch stripe;
a store: the keys and values cut out of the final chunk's stripe, read and
written) over the chip's peak HBM bandwidth, over the device time of the two
programs (``jit_store_snapshot``, ``jit_seed_prefix``), percent; on the traced
window's own counts."""

from benchmark import ssm_gqa_dense

read = ssm_gqa_dense.on_window(ssm_gqa_dense.snapshot_copy_share)

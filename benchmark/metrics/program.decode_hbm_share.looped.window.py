"""Bytes a decode step of a ``looped_dense`` model must move (the layers'
weights once a pass, the head once, the live slots' keys and values in every
row of a cache with a row a pass and layer: ``family.step_needed_bytes``) over
the chip's peak HBM bandwidth, over the device time of a step
(``jit_decode_fn``), percent; on the traced window's own counts. The share of
the whole step that bounds every later claim in this cell."""

from benchmark import looped

read = looped.on_window(looped.step_share)

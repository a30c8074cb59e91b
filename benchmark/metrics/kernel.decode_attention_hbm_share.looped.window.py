"""Bytes of the live slots' keys and values in every row of a ``looped_dense``
model's cache (a row a pass and layer) over the chip's peak HBM bandwidth,
over the device time of a decode step under ``attn_core``, percent; on the
traced window's own counts. The decode kernel's roofline share at this
family's row count."""

from benchmark import looped

read = looped.on_window(looped.attention_share)

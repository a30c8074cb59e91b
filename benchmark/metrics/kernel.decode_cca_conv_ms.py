"""Device milliseconds of one decode step (``jit_decode_fn``) under
``cca_conv``, all layers: both convolutions through the slots' tails, the mean,
the norm a head, the temperature, the rotation, the shifted values and the
tail's read and write. Many small operations: the number that says whether
they want fusing. None against a program without the scope."""

from benchmark import cca_moe

read = cca_moe.conv_ms

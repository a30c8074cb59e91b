"""Device milliseconds of one decode step (``jit_decode_fn``) under
``moe_ffn/router`` of a model whose router is an MLP with a stream of its own
through the depth (family ``cca_moe``), all layers: the projection, the
multiple of the layer before's vector, the norm, three small matrices, the
choice and the routing counts. None against any other program."""

from benchmark import cca_moe

read = cca_moe.router_ms

"""Program counter: bytes of keys and values a token holds in the engine's
pool, as ``get_stats()`` says: a row a pass and layer of a model whose stack
runs several times a token."""

from benchmark import looped

read = looped.kv_bytes_per_token

"""Start-up: seconds inside the backend's compiler before the window opened
(the ``backend`` ``xla.compile`` spans of cache misses that start before
it; a miss's span holds the key's hashing and the write to the cache too)."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.before_opening(
        obs, lambda s: _compiles.is_backend(s, "compiled")), _compiles.seconds)

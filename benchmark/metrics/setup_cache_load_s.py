"""Start-up: seconds spent reading executables out of the persistent
compile cache and loading them onto the device before the window opened
(``retrieval_s`` of the ``backend`` ``xla.compile`` spans that hit, JAX's
``cache_retrieval_time_sec``). What such a span lasts beyond it is the
hashing of the lowered module into the key."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.before_opening(
        obs, lambda s: _compiles.is_backend(s, "loaded")), _compiles.counted_seconds)

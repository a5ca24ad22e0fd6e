"""Start-up: executables LOADED from the persistent compile cache before
the window opened (``xla.compile`` spans of phase ``backend`` with
``outcome`` ``loaded``, counted by their start). With
``setup_programs_compiled`` it is every program set-up asked for."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.before_opening(
        obs, lambda s: _compiles.is_backend(s, "loaded")))

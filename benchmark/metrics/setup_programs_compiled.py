"""Start-up: executables the backend COMPILED before the window opened
(``xla.compile`` spans of phase ``backend`` with ``outcome`` ``compiled``,
counted by their start). A count the program decides: which programs it
asks for and whether the persistent cache had them; no shared host core
moves it."""

from benchmark.metrics import _compiles


def read(obs):
    return _compiles.total(_compiles.before_opening(
        obs, lambda s: _compiles.is_backend(s, "compiled")))

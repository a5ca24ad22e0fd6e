"""Model step, serving: the share of the decode program's device time that
names no part of the model: operations inside the ``jit_step`` executions
of the traced window with no ``op_name``, with one made of the program and
its loop alone (conversions and copies the compiler hoists out of the
scan), or with a name that two programs put under different scopes
(``_scopes``). A scope's share that falls while this one rises by as much
has moved, not shrunk."""

from benchmark.metrics import _scopes


def read(obs):
    return _scopes.share_of_program(obs, _scopes.names_no_part)

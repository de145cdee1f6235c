"""A test-only model family: the ``dense`` family with every call counted.

``test_bench.py`` serves a configuration that names it and checks that
each weight, reference and accounting call of the run came through here,
and that the run reads exactly what the ``dense`` family's run reads.
"""

from collections import Counter

from families import dense

CALLS = Counter()
CONTROLS = dense.CONTROLS


def _counted(name):
    inner = getattr(dense, name)

    def fn(*args, **kwargs):
        CALLS[name] += 1
        return inner(*args, **kwargs)
    return fn


dims = _counted("dims")
make_weights = _counted("make_weights")
program_params = _counted("program_params")
arch_config = _counted("arch_config")
pool_row_bytes = _counted("pool_row_bytes")
gaps = _counted("gaps")
decode_bytes = _counted("decode_bytes")
walk_bytes = _counted("walk_bytes")
flops = _counted("flops")

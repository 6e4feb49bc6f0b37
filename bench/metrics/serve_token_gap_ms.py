"""Gap between tokens: mean over the rounds in the traced window of the
engine's ``serve.decode`` span over the ``serve.step`` spans in it, in
ms."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.token_gap_ms(pt)

"""Host time to dispatch a decode step: mean over every ``serve.step``
span in the traced window but each round's first of the span less the
``serve.sync`` (``block_until_ready``) inside it, in ms."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.dispatch_ms(pt)

"""Mean over the rounds in the traced window of each round's first
``serve.step`` span: the retrace, lowering and compile-cache load of the
decode step (``serve.trace_step``) and the step itself, in ms."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.first_step_ms(pt)

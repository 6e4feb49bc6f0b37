"""Mean ``data.batch`` span of the input pipeline in the traced window:
the host making one batch, without its ``device_put``, in ms."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.span_ms(pt, "data.batch")

"""Mean ``serve.prefill_step`` span of the serve engine in the traced
window: one batched prefill of a round's whole prompt, its dispatch and
its sync, in ms.  A program that prefills token by token has no such
span, and reads nothing."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.span_ms(pt, "serve.prefill_step")

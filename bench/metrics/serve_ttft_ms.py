"""Time to first token: mean over the rounds in the traced window of the
engine's ``serve.generate`` span start to its ``serve.prefill`` span end
(the first new token's logits ready), in ms."""
from bench import progtrace


def read(run, result):
    pt = progtrace.of(result)
    return None if pt is None else progtrace.ttft_ms(pt)

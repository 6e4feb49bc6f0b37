"""Mean of the engine's own ``serve.step_seconds`` histogram over the
window: (sum, count) read before and after it."""


def read(run, result):
    w = result["window"]
    return 1e3 * w["serve_step_seconds"] / w["serve_steps"] if w[
        "serve_steps"] else None

"""Mean host time per step to make the batch and hand it to the device
(``stream.batch(i)`` and its ``device_put``), over the window."""


def read(run, result):
    xs = result["window"]["input_seconds"]
    return 1e3 * sum(xs) / len(xs) if xs else None

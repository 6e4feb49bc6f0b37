"""The training window: at most IN_FLIGHT steps queued, and only completed
steps counted, over the time to the last completion."""
import time

import pytest

from bench import common, manifest

drive_train = manifest.load_driver("train")


class FakeLoss:
    def __init__(self, book, ready_at):
        self.book, self.ready_at, self.done = book, ready_at, False
        book["queued"] += 1
        book["max_queued"] = max(book["max_queued"], book["queued"])

    def block_until_ready(self):
        while time.perf_counter() < self.ready_at:
            pass
        if not self.done:
            self.done = True
            self.book["queued"] -= 1
            self.book["completed"] += 1
        return self


def fake_ctx(step_s):
    book = {"queued": 0, "max_queued": 0, "completed": 0, "dispatched": 0,
            "device_free": 0.0}

    def step(state, batch):
        # a device that runs the queued steps one after another
        start = max(time.perf_counter(), book["device_free"])
        book["device_free"] = start + step_s
        book["dispatched"] += 1
        return state + 1, {"loss": FakeLoss(book, book["device_free"])}

    ctx = {"compiled": step, "feed": lambda i: i, "state": 0, "next_step": 3,
           "global_batch": 8, "flops_per_step": 1.0}
    return ctx, book


def test_at_most_in_flight_steps_and_only_completed_counted():
    ctx, book = fake_ctx(step_s=0.002)
    w = drive_train.window(ctx, 0.2, common.spans(False))
    assert book["max_queued"] <= drive_train.IN_FLIGHT
    assert w["steps"] == book["completed"] == book["dispatched"]
    assert book["queued"] == 0
    assert w["samples"] == 8 * w["steps"]
    assert ctx["state"] == w["steps"] and ctx["next_step"] == 3 + w["steps"]
    # the device was never starved: the rate is the fake device's own
    assert w["steps"] == pytest.approx(w["seconds"] / 0.002, rel=0.1)
    assert w["gap_median_s"] == pytest.approx(0.002, rel=0.5)
    assert w["gap_max_s"] >= w["gap_median_s"]


def test_window_ends_at_the_last_completion():
    ctx, book = fake_ctx(step_s=0.05)
    t0 = time.perf_counter()
    w = drive_train.window(ctx, 0.01, common.spans(False))
    # IN_FLIGHT steps dispatched in the 10 ms, none after; the window
    # waits for the last of them
    assert w["steps"] == book["dispatched"] == drive_train.IN_FLIGHT
    assert w["seconds"] >= 0.05 * drive_train.IN_FLIGHT
    assert time.perf_counter() - t0 >= w["seconds"]


def test_a_real_step_at_reduced_size():
    """The launcher's own step on the CPU: the window's rate is every
    completed step's samples over the whole window."""
    import jax

    from bench import run as bench_run
    run = bench_run.plan("dlrm-train-b8192-dev", 12345)
    run["config"]["model"].update(n_layers=2, d_model=32, mlp_widths=[32, 32])
    run["traffic"]["global_batch"] = 16
    run.update(devices=jax.devices()[:1], times={}, trace=False)
    ctx = drive_train.setup(run)
    w = drive_train.window(ctx, 0.3, common.spans(False))
    assert w["steps"] > drive_train.IN_FLIGHT
    assert w["samples"] == 16 * w["steps"]
    assert ctx["next_step"] == drive_train.CHECK_STEPS + w["steps"]
    assert sorted(run["times"]) == ["compile", "first_steps", "import_program",
                                    "inputs", "weights"]

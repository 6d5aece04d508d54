"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a tiny cell on the CPU (the harness's
look for a chip skipped) with one fault planted in the program: the step
returns its state unchanged, half of each device's batch is left out,
the eval's accuracy answer is altered, or the chips' results are not
exchanged.  The unbroken run comes out correct.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("faults")))


@pytest.fixture(autouse=True)
def fresh_chunks():
    """The driver reuses a jitted chunk across calls in one process, keyed
    on what the chunk closes over and not on the round body: each test
    starts and ends with no cached chunk, so a fault planted in the body
    is traced into the run and stays out of the next test."""
    from repro.fl import driver

    driver.clear_chunk_cache()
    yield
    driver.clear_chunk_cache()


def _run(bench, cell="tiny_mlp"):
    return harness.run(cell, 11, 0.5, False, bench, time.monotonic(),
                       require_chip=False)


def test_unbroken_run_is_correct(bench):
    res = _run(bench)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_state_left_unchanged(bench, monkeypatch):
    from repro.fl import driver

    orig = driver.make_round_body

    def frozen(*a, **kw):
        body = orig(*a, **kw)

        def step(scheme, eta, params, *rest):
            _, fstate, metrics = body(scheme, eta, params, *rest)
            return params, fstate, metrics
        return step

    monkeypatch.setattr(driver, "make_round_body", frozen)
    res = _run(bench)
    assert res["correct"] is False
    assert res["checks"]["delta"]["value"] > 0.5


def test_half_the_batch_left_out(bench, monkeypatch):
    from repro import tasks

    orig = tasks.get

    def halved(*a, **kw):
        task = orig(*a, **kw)
        loss = task.loss_fn

        def half(params, batch):
            x, y = batch
            keep = x.shape[0] // 2
            return loss(params, (x[:keep], y[:keep]))
        return dataclasses.replace(task, loss_fn=half)

    monkeypatch.setattr(tasks, "get", halved)
    res = _run(bench)
    assert res["correct"] is False
    assert res["checks"]["grad_norm"]["value"] > \
        res["checks"]["grad_norm"]["limit"]


def test_answer_altered_where_produced(bench, monkeypatch):
    from repro.models import mlp

    orig = mlp.accuracy
    monkeypatch.setattr(mlp, "accuracy",
                        lambda p, x, y: orig(p, x, (y + 1) % 10))
    res = _run(bench)
    assert res["correct"] is False
    assert res["checks"]["acc"]["value"] > res["checks"]["acc"]["limit"]


EXCHANGE = """
import json, sys, time
sys.path[:0] = {paths!r}
from bench import harness
from bench.calibrate import exchange_left_out
with exchange_left_out():
    res = harness.run("tiny_grid", 11, 0.5, False, {bench!r},
                      time.monotonic(), require_chip=False)
print(json.dumps(res))
"""


def test_exchange_between_chips_left_out(bench):
    root = os.path.dirname(bench)
    code = EXCHANGE.format(paths=[root, os.path.join(tiny.ROOT, "src"),
                                  tiny.ROOT], bench=bench)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is False
    assert res["checks"]["delta"]["value"] > res["checks"]["delta"]["limit"]

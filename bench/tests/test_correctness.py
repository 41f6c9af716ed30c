"""``correct`` at tiny sizes on the CPU: a sound run passes; a run with its
timed path broken underneath fails; and the control (the reference one step
down in precision, in the program's place) reads above the limits.

The harness's look for a chip is skipped (``require_tpu=False``); the rest of
a run is the benchmark's own, its load generator process included.  The
limits here are the tiny sizes' own; the cell's limits, set from chip
readings, are in its configuration file.
"""
import json

import pytest

import run as bench_run
import tiny
from harness.common import CompileEvents, device_info
from harness.spec import load_cell

LIMITS = {"logit_gap": 0.004, "logprob_gap": 0.02}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"), LIMITS)


@pytest.fixture(autouse=True)
def cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def result(root, capsys, seed):
    rc = bench_run.main(["--workload", tiny.SERVE, "--seed", str(seed), "--seconds", "1",
                         "--trace", "0"], root=root, require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(root, capsys):
    out = result(root, capsys, 2**33 + 5)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks" and out["failed"] == 0
    assert set(out["checks"]) == set(LIMITS)


FAULTS = {
    # the served token altered where it is produced
    "token": lambda nxt, lp, tokens, lengths: ((nxt + 1) % 512, lp),
    # the served logprob altered where it is produced
    "logprob": lambda nxt, lp, tokens, lengths: (nxt, lp - 0.1),
}


@pytest.mark.parametrize("fault", ["token", "logprob", "padding"])
def test_broken_serve_path_is_not_correct(root, capsys, monkeypatch, fault):
    import repro.serving.service as service

    real = service.score_tokens

    def broken(model, params, tokens, lengths):
        if fault == "padding":  # every row answered from the last padded position
            return real(model, params, tokens, lengths * 0 + tokens.shape[1])
        return FAULTS[fault](*real(model, params, tokens, lengths), tokens, lengths)

    monkeypatch.setattr(service, "score_tokens", broken)
    out = result(root, capsys, 12)
    assert not out["correct"], out["checks"]


def test_control_fails_a_limit(root):
    import calibrate

    cell = load_cell(tiny.SERVE, root)
    _, devs = device_info(1)
    got = calibrate.calibrate(cell, 13, 2.0, devs, CompileEvents(), control=True)
    limits = cell.config["limits"]
    assert all(got["program"][k] <= v for k, v in limits.items()), got
    assert any(got["control_fp8"][k] > v for k, v in limits.items()), got

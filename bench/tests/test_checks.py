"""The comparisons that decide ``correct`` fail when they should: the
control (the reference one step below the stated precision, or with a
stated guarantee broken) reads past each limit, and a run whose timed path
is broken underneath (an answer or token altered where it is produced)
comes out not correct. CPU, at sizes a test run holds."""
import json

import numpy as np
import pytest

from bench import harness, load_json, load_module
from bench import control as control_script

SEED = 3_000_000_023
LM_CELLS = ["qwen3-1.7b.chat-mixed"]
#: qwen3-1.7b at its published widths, four layers and an 8,192-token slice
#: of the vocabulary: logits of the real scale, at a size the CPU holds
LM_WIDE = {"num_hidden_layers": 4, "vocab_size": 8192}


def run(capsys, cell, overrides) -> dict:
    rc = harness.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       "1", "--trace", "0"], allow_cpu=True,
                      overrides=overrides)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def lm_overrides(cell):
    traffic = load_json("traffic", cell.rsplit(".", 1)[1])["smoke"]
    traffic = json.loads(json.dumps(traffic))
    traffic["fields"]["prompt"]["high"] = LM_WIDE["vocab_size"]
    return {"config": dict(LM_WIDE), "traffic": traffic}


# -- WAH -------------------------------------------------------------------
def test_wah_control_breaks_the_index():
    ref = load_module("reference", "wah")
    rng = np.random.default_rng(7)
    values = rng.zipf(1.5, 4096).clip(max=256).astype(np.uint32) - 1
    want, starts, counts = ref.wah_index(values, 256)
    got, _, _ = ref.wah_index(values, 256, stable=False)
    assert got.shape != want.shape or np.count_nonzero(got != want) > 0
    # the plain reference decodes back to the input
    for v in (0, 1, 5):
        words = want[starts[v]:starts[v] + counts[v]]
        pos, group = [], 0
        for w in words:
            if w >> 31:
                group += int(w) & ((1 << 30) - 1)
            else:
                pos += [group * 31 + b for b in range(31) if w >> b & 1]
                group += 1
        assert pos == list(np.flatnonzero(values == v))


def test_wah_control_reads_past_the_limit(capsys):
    rc = control_script.main(["--workload", "wah.build-2e24", "--seconds",
                              "1", "--seeds", "5", "--control-seeds", "5"],
                             allow_cpu=True, overrides={"smoke": True})
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["program"]["wrong_words"] == 0
    assert line["control"]["wrong_words"] > 0


def test_wah_build_answer_altered(capsys, monkeypatch):
    import repro.indexing

    real = repro.indexing.build_wah_index

    def broken(values, cardinality):
        words, n, starts, counts = real(values, cardinality)
        return words.at[0].set(words[0] ^ 1), n, starts, counts

    monkeypatch.setattr(repro.indexing, "build_wah_index", broken)
    out = run(capsys, "wah.build-2e24", {"smoke": True})
    assert out["correct"] is False
    assert out["checks"]["wrong_words"]["value"] > 0


# -- served LM -------------------------------------------------------------
@pytest.mark.parametrize("cell", LM_CELLS)
def test_lm_program_passes_and_control_fails(capsys, cell):
    limit = load_json("configs", "qwen3-1.7b")["limits"]["max_logit_gap"]
    # two seconds of traffic: some forty served tokens to compare
    rc = control_script.main(["--workload", cell, "--seconds", "2",
                              "--seeds", "5", "--control-seeds", "5"],
                             allow_cpu=True, overrides=lm_overrides(cell))
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["failed"] == 0, line
    assert line["program"]["max_logit_gap"] <= limit
    assert line["control"]["max_logit_gap"] > limit


def test_lm_token_altered(capsys, monkeypatch):
    import jax.numpy as jnp
    from repro.dist import step

    real = step.build_serve_step

    def broken(model):
        inner = real(model)

        def serve_step(params, cache, tokens):
            nxt, logits, cache = inner(params, cache, tokens)
            # the batch's first request gets its least likely token
            worst = jnp.argmin(logits[:, -1:, :], axis=-1).astype(nxt.dtype)
            return nxt.at[0].set(worst[0]), logits, cache

        return serve_step

    monkeypatch.setattr(step, "build_serve_step", broken)
    out = run(capsys, "qwen3-1.7b.chat-mixed",
              lm_overrides("qwen3-1.7b.chat-mixed"))
    assert out["correct"] is False
    assert (out["checks"]["max_logit_gap"]["value"]
            > out["checks"]["max_logit_gap"]["limit"])

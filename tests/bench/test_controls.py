"""`correct` can come out false. At a toy size on the CPU, on three seeds:
the program passes under limits that the control (the reference computed in
the nearest precision below the one the toy configuration states, bfloat16
for float32) fails. And with the timed path broken underneath (a step that
returns its state unchanged; a token altered where it is produced; requests
due in the window that never ended) a whole run reports `correct: false`. The same readings at the cells' own sizes on
the chip are in PERF.md; benchmarks/tools/control.py takes them."""

import ast
import json

import pytest

from bench_helpers import run_tiny, tiny_root  # noqa: F401

SEEDS = (11, 12, 13)


def _limits(root, config):
    with open(f"{root}/benchmarks/configs/{config}.json") as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_where_the_program_passes(tiny_root, seed):
    root, _ = tiny_root
    result, lines = run_tiny(root, "tiny-train-fp32", seed=seed,
                             control="bf16")
    assert result["correct"] is True
    control = ast.literal_eval(next(
        l for l in lines if l.startswith("control bf16:")).split(": ", 1)[1])
    limits = _limits(root, "tiny-gpt2-fp32")["train"]
    # the lower precision has to fail one of the cell's numbers (here it
    # fails all three), by a factor of three or more
    assert all(control[k] > 3 * limits[k] for k in limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_where_the_program_passes(tiny_root, seed):
    root, _ = tiny_root
    result, lines = run_tiny(root, "tiny-chat-fp32", seed=seed,
                             seconds=0.5, control="bf16")
    assert result["correct"] is True
    widest = float(next(l for l in lines if l.startswith(
        "control bf16:")).split("widest ")[1].split(" ")[0])
    assert widest > 3 * _limits(root, "tiny-gpt2-fp32")["serve"]["token_gap"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_root, monkeypatch):
    from bigdl_tpu.optim import optimizer as opt_mod

    make = opt_mod.LocalOptimizer._make_step

    def broken(self):
        step = make(self)

        def unchanged(params, mod_state, slots, *rest):
            import jax

            keep = jax.tree_util.tree_map(lambda a: a + 0, (params, slots))
            _, new_state, _, loss = step(params, mod_state, slots, *rest)
            return keep[0], new_state, keep[1], loss

        return unchanged

    monkeypatch.setattr(opt_mod.LocalOptimizer, "_make_step", broken)
    root, _ = tiny_root
    result, lines = run_tiny(root, "tiny-train-fp32", seed=3)
    assert result["correct"] is False
    assert any(l.startswith("check delta_gap") and "NOT OK" in l
               for l in lines)


def test_an_altered_token_is_not_correct(tiny_root, monkeypatch):
    from bigdl_tpu.serving import engine as eng_mod

    sample = eng_mod.sample_logits

    def altered(logits, *args, **kw):
        return (sample(logits, *args, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(eng_mod, "sample_logits", altered)
    root, _ = tiny_root
    result, lines = run_tiny(root, "tiny-chat-fp32", seed=4, seconds=0.5)
    assert result["correct"] is False
    assert any(l.startswith("check token_gap") and "NOT OK" in l
               for l in lines)


def test_a_request_that_never_ended_is_failed_and_not_correct(tiny_root):
    """The open loop's tail cut to nothing: what was due in the window and
    had not ended is counted in `attempted` AND in `failed`, never dropped."""
    root, _ = tiny_root
    result, lines = run_tiny(root, "tiny-chat-cut", seed=5, seconds=0.5)
    counters = ast.literal_eval(next(
        l for l in lines if l.startswith("serve "))[6:])
    assert counters["requests_unfinished"] > 0
    assert result["attempted"] == counters["requests_due_or_done"]
    assert result["failed"] == (counters["requests_due_or_done"]
                                - counters["requests_timed"]) > 0
    assert result["correct"] is False
    assert any(l.startswith("check requests_done") and "NOT OK" in l
               for l in lines)

"""The `mla_moe` family at a toy size on the CPU: the plain reference
against the program, LOGITS against logits; each term of the mathematics
left out of a copy of the reference fails that comparison; the cell's driver
end to end with the float8 control failing where the program passes.

The toy configuration (tests/bench/tiny_mla_moe/) has the layer kinds and
every mechanism of benchmarks/configs/joyai-llm-flash.json at widths of a
few dozen: 1 dense + 2 MoE layers, 8 experts top-2 with a shared one, the
selection bias, the 2.5 factor, interleaved RoPE, both norms of the latent
path. Its weights are the reference's bfloat16-valued ones held in float32
and both sides compute in float32, so what is left between them is the
order of summation:

  TIGHT = 2e-5 on logits of order 0.3 (measured 6e-8 to 4e-7): a hundred
  times the rounding seen, under a hundredth of the smallest left-out term
  below (RoPE pairing the wrong dimensions moves a logit by 3.2e-3, the
  others by more).
"""

import inspect
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import REPO, run_tiny, tree_hashes
from benchmarks.families import mla_moe as fam
from benchmarks.harness import manifest as mf
from benchmarks.reference import mla_moe as ref

TINY = os.path.join(REPO, "tests", "bench", "tiny_mla_moe")
CELL, REAL_CELL = "tiny-mla-moe-backlog", "joyai-flash-serve-backlog"
TIGHT = 2e-5


def _cfg():
    with open(os.path.join(TINY, "configs", "tiny-mla-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    """(cfg, reference params, program model, program variables, tokens,
    the reference's logits): everything from seed 5."""
    cfg = _cfg()
    params = fam.reference_params(5, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, toks, cfg)
    return (cfg, params, fam.program_model(cfg), fam.make_variables(5, cfg),
            toks, want)


def _paged_logits(model, variables, toks, prompt_len, block=4, bucket=32):
    """Prefill `prompt_len` tokens of one sequence (padded to `bucket`),
    then decode the rest a token at a time: the logits of every position
    from `prompt_len - 1` on, as the engine produces them (it re-decodes
    the last prompt token)."""
    n = toks.shape[0]
    nb = -(-n // block)
    pools = model.init_block_pool(1 + nb, block)
    table = np.arange(1, 1 + nb, dtype=np.int32)[None]
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = toks[:prompt_len]
    pools = model.prefill_paged(variables, jnp.asarray(padded), pools,
                                jnp.asarray(table),
                                jnp.asarray(table[0, :bucket // block]), 0)
    out = []
    for t in range(prompt_len - 1, n):
        lg, pools, _ = model.decode_step_paged(
            variables, toks[t:t + 1], jnp.asarray([t]), pools,
            jnp.asarray(table))
        out.append(lg[0])
    return jnp.stack(out)


def test_the_weights_are_the_references(toy):
    cfg, params, _, variables, _, _ = toy
    p = variables["params"]
    assert p["layers"][1]["moe"]["w_gate"].dtype == jnp.float32
    np.testing.assert_array_equal(
        p["layers"][1]["moe"]["w_gate"], params["layers"][1]["e_g"])
    np.testing.assert_array_equal(p["layers"][0]["wkv_a"],
                                  params["layers"][0]["w_dkv"])
    assert float(jnp.abs(params["layers"][2]["b_r"]).max()) > 0.05
    assert ref.layer_kinds(cfg) == ["dense", "moe", "moe"]


def test_full_forward_equals_the_reference(toy):
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(variables, toks)
    assert float(jnp.max(jnp.abs(got - want))) < TIGHT


@pytest.mark.parametrize("prompt_len", [5, 17, 32])
def test_prefill_then_decode_equals_the_references_full_forward(
        toy, prompt_len):
    """The cache rows come from the naive prefill, the logits from the
    absorbed decode: both against the reference, which has neither."""
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, variables, toks[0], prompt_len)
    assert float(jnp.max(jnp.abs(got - want[0, prompt_len - 1:]))) < TIGHT


def test_the_engine_serves_what_the_reference_puts_first(toy):
    """Through `InferenceEngine` + `EngineRouter`, greedy: every served
    token is the reference's best at its position, to within TIGHT."""
    from bigdl_tpu.serving import EngineRouter, InferenceEngine, Request

    cfg, params, model, variables, _, _ = toy
    engine = InferenceEngine(model, variables, slots=3, max_len=64,
                             prefill_buckets=(16, 32), block_size=4)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (5, 13, 30, 16, 9)]
    results = EngineRouter([engine]).run(
        [Request(prompt=p, max_new_tokens=7) for p in prompts])
    assert [r.status for r in results] == ["done"] * len(prompts)
    with jax.default_matmul_precision("highest"):
        for p, r in zip(prompts, results):
            seq = jnp.asarray([(p + r.tokens)[:-1]])
            lg = ref.logits(params, seq, cfg)[0, len(p) - 1:]
            gap = jnp.max(lg, -1) - lg[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < TIGHT


# (what is left out, the line of the reference, what stands there instead)
ABLATIONS = [
    ("shared_expert",
     "return y + _gated(h, lp[\"s_g\"], lp[\"s_u\"], lp[\"s_d\"], precision)",
     "return y"),
    ("routed_scaling_factor",
     "w = w * cfg[\"routed_scaling_factor\"]", "pass"),
    ("weight_normalisation",
     "w = w / jnp.sum(w, -1, keepdims=True)", "pass"),
    ("selection_bias",
     "lax.top_k(s + lp[\"b_r\"], cfg[\"num_experts_per_tok\"])",
     "lax.top_k(s, cfg[\"num_experts_per_tok\"])"),
    ("rope_interleaving",
     "if cfg.get(\"rope_interleave\", True):", "if False:"),
    ("attention_scale",
     "scale = cfg.get(\"qk_head_dim\", nope + q_rope.shape[-1]) ** -0.5",
     "scale = nope ** -0.5"),
    ("c_kv_norm",
     "c_kv = _rms(lat[:, :rank], lp[\"kv_norm\"], eps)",
     "c_kv = lat[:, :rank]"),
]


@pytest.mark.parametrize("name,line,instead", ABLATIONS,
                         ids=[a[0] for a in ABLATIONS])
def test_a_reference_with_a_term_left_out_fails_the_comparison(
        toy, name, line, instead):
    """A COPY of the reference's source with one line changed: the program
    no longer agrees with it, by a hundred times the tolerance or more,
    so the comparison would catch the term missing from the program."""
    cfg, params, model, variables, toks, want = toy
    source = inspect.getsource(ref)
    assert source.count(line) == 1, f"the reference no longer has: {line}"
    copy = types.ModuleType(f"mla_moe_without_{name}")
    exec(compile(source.replace(line, instead), copy.__name__, "exec"),
         copy.__dict__)
    with jax.default_matmul_precision("highest"):
        ablated = copy.logits(params, toks, cfg)
        got = _paged_logits(model, variables, toks[0], 17)
    assert float(jnp.max(jnp.abs(ablated - want))) > 100 * TIGHT
    assert float(jnp.max(jnp.abs(got - ablated[0, 16:]))) > 100 * TIGHT


def test_lower_precisions_differ_from_the_reference(toy):
    """The float8 control, and the program computing in bfloat16 where the
    file says float32, both miss the reference by far more than TIGHT."""
    cfg, params, _, _, toks, want = toy
    with jax.default_matmul_precision("highest"):
        fp8 = ref.logits(params, toks, cfg, "fp8")
        low = dict(cfg, dtype={"weights": "bfloat16", "cache": "bfloat16"})
        bf16 = _paged_logits(fam.program_model(low),
                             fam.make_variables(5, low), toks[0], 17)
    assert 50 * TIGHT < float(jnp.max(jnp.abs(bf16 - want[0, 16:]))) < 0.05
    assert 500 * TIGHT < float(jnp.max(jnp.abs(fp8 - want))) < 0.2
    with pytest.raises(ValueError, match="precision"):
        ref.logits(params, toks, cfg, "bf16")


def test_counts_match_the_published_sizes():
    from benchmarks.counts import mla_moe as counts

    with open(os.path.join(
            REPO, "benchmarks/configs/joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    shapes = jax.eval_shape(lambda: ref.init(0, cfg))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == counts.params_held(cfg)
    assert 5.55e9 < n < 5.57e9              # ISSUE 28's table: 5,558M
    assert counts.expert_params(cfg) == 3 * 2048 * 768
    assert counts.attention_params(cfg) == 26_347_520
    # the whole 40-layer model: "48B"
    whole = dict(cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"])
    assert 47e9 < counts.params_held(whole) < 50e9
    # a decode step that touches every expert reads every weight but the
    # embedding once; one that touches none, 0.92 GB
    full = counts.decode_bytes_per_step(cfg, 4 * 256, 0)
    assert full == 2 * (n - 2048 * cfg["vocab_size"])
    assert 0.9e9 < counts.decode_bytes_per_step(cfg, 0, 0) < 0.95e9
    assert counts.decode_bytes_per_step(cfg, 0, 1000) - \
        counts.decode_bytes_per_step(cfg, 0, 0) == 2 * 1000 * 5 * 576


# ------------------------------------------------- the driver, end to end

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """benchmarks/ copied, the toy configuration and traffic laid beside
    the real files, and a manifest with one cell that reports what the
    real cell reports."""
    root = str(tmp_path_factory.mktemp("mla_moe_root"))
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = tree_hashes(os.path.join(root, "benchmarks"))
    for sub in ("configs", "traffic"):
        for f in os.listdir(os.path.join(TINY, sub)):
            dst = os.path.join(root, "benchmarks", sub, f)
            assert not os.path.exists(dst), f"{f} would replace a file"
            shutil.copy(os.path.join(TINY, sub, f), dst)
    real = mf.load(REPO)
    manifest = dict(real, run_seconds=1, configs=[{
        "name": "tiny-mla-moe", "source": "none: a toy size",
        "file": "benchmarks/configs/tiny-mla-moe.json", "reduced": [],
        "why": "tests only"}], workloads=[{
            "name": CELL, "config": "tiny-mla-moe",
            "traffic": "tiny-longgen-backlog", "chips": 1,
            "why": "CPU rehearsal; no number of it is a measurement"}])
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[CELL]) if "workloads" in m else m
            for m in real[group]
            if REAL_CELL in m.get("workloads", [REAL_CELL])]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield root, before
    from jax.experimental.compilation_cache import compilation_cache

    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    from bigdl_tpu import obs

    obs.set_tracer(None)


def test_the_cell_is_files_only(tiny_root):
    root, before = tiny_root
    after = tree_hashes(os.path.join(root, "benchmarks"))
    assert {k: after[k] for k in before} == before
    assert mf.problems(mf.load(root), root) == []
    real = mf.load(REPO)
    names = {m["name"] for m in mf.metrics_of(
        real, mf.cell_of(real, REAL_CELL), "per_layer")}
    assert {"moe_decode_roofline", "moe_expert_load_max_over_mean",
            "backlog_decode_step_p50", "backlog_peak_hbm"} <= names


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12])
def test_the_control_fails_where_the_program_passes(tiny_root, seed):
    root, _ = tiny_root
    result, lines = run_tiny(root, CELL, seed=seed, control="fp8")
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_throughput", "setup_s"}
    widest, mean = (float(next(l for l in lines if l.startswith(
        "control fp8:")).split(word)[1].split(" ")[0])
        for word in ("widest ", "mean "))
    limits = _cfg()["limits"]["serve"]
    assert widest > 3 * limits["token_gap"]
    assert mean > 3 * limits["token_gap_mean"]


def test_a_traced_run_reads_the_new_spans(tiny_root):
    """On the CPU there is no device trace, so the roofline share is left
    out; what the spans carry is read."""
    from bigdl_tpu import obs

    root, _ = tiny_root
    result, lines = run_tiny(root, CELL, seed=13, trace=True)
    assert result["correct"] is True
    assert "moe_decode_roofline" not in result["metrics"]
    load = result["metrics"]["moe_expert_load_max_over_mean"]["value"]
    assert 1.0 <= load <= 8.0           # 8 experts: at most all on one
    assert 0 < result["metrics"]["backlog_queue_left"]["value"] < 100
    steps = [e["args"] for e in obs.get_tracer().events("decode_step")]
    assert steps and all(
        len(a["experts_touched"]) == 2 and a["cached_tokens"] > 0
        and all(1 <= n <= 8 for n in a["experts_touched"]) for a in steps)
    assert all(e["args"]["moe_assignments"] == 2 * e["args"]["bucket"]
               for e in obs.get_tracer().events("prefill"))

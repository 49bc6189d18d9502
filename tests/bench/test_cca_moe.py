"""The `zaya` family at a toy size on the CPU: the plain reference against
the program, LOGITS against logits, through `apply`, through prefill then
decode, and through the engine with requests that join and leave mid-way;
each [paper] term of the mathematics left out of a copy of the reference
fails that comparison; the counts against the program's parameters at the
cell's configuration; the cell's driver end to end with the float8 control
failing where the program passes, and both new readers returning a number.

The toy configuration (tests/bench/tiny_cca_moe/) has every mechanism of
benchmarks/configs/zaya1-8b.json at widths of a few dozen, the ratios kept:
8 query heads over 2 key-value heads of 16, 2 + 2 taps, 16 + 1 router
outputs, one expert a token, half of a head rotated, a tied head, three of
six published layers. Its weights are the reference's bfloat16-valued ones
held in float32 and both sides compute in float32, so what is left between
them is the order of summation:

  TIGHT = 2e-5 on logits of order 1 (measured under 3e-6): eight times the
  rounding seen, under a hundredth of the smallest left-out term below.
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
from benchmarks.families import cca_moe as fam
from benchmarks.harness import manifest as mf
from benchmarks.reference import cca_moe as ref

TINY = os.path.join(REPO, "tests", "bench", "tiny_cca_moe")
CELL, REAL_CELL = "tiny-cca-moe-backlog", "zaya1-8b-serve-backlog"
TIGHT = 2e-5


def _cfg():
    with open(os.path.join(TINY, "configs", "tiny-cca-moe.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    """(cfg, reference params, program model, program variables, tokens,
    the reference's logits): everything from seed 5."""
    cfg = _cfg()
    params = fam.reference_params(5, cfg)
    # two of the sequences the routers' biases are evened out over (few
    # repeats, many): sixteen drawn tokens and the answer's first 24, so
    # that every router output, the skip too, gets some of their rows
    toks = ref.balance_tokens(jnp.uint32(5), cfg)[jnp.asarray([1, 6]), :40]
    with jax.default_matmul_precision("highest"):
        want = ref.logits(params, toks, cfg)
    return (cfg, params, fam.program_model(cfg), fam.make_variables(5, cfg),
            toks, want)


def _paged_logits(model, variables, toks, prompt_len, block=4, bucket=32,
                  cache=jnp.float32):
    """Prefill `prompt_len` tokens of one sequence (padded to `bucket`)
    into slot 0, then decode the rest a token at a time: the logits of
    every position from `prompt_len - 1` on, as the engine produces them
    (it re-decodes the last prompt token)."""
    n = toks.shape[0]
    nb = -(-n // block)
    pools = model.init_block_pool(1 + nb, block, cache, slots=1)
    table = np.arange(1, 1 + nb, dtype=np.int32)[None]
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = toks[:prompt_len]
    ids = {"table": jnp.asarray(table[0, :bucket // block]),
           "state": {"slot": np.int32(0), "keep": np.int32(prompt_len - 2)}}
    pools = model.prefill_paged(variables, jnp.asarray(padded), pools,
                                jnp.asarray(table), ids, 0)
    out = []
    for t in range(prompt_len - 1, n):
        lg, pools, _ = model.decode_step_paged(
            variables, toks[t:t + 1], jnp.asarray([t]), pools,
            jnp.asarray(table))
        out.append(lg[0])
    return jnp.stack(out)


def test_the_weights_are_the_references(toy):
    cfg, params, model, variables, _, _ = toy
    p = variables["params"]
    first, later = params["layers"][0], params["layers"][2]
    assert p["layers"][1]["experts"]["w_gate"].dtype == jnp.float32
    np.testing.assert_array_equal(p["layers"][2]["experts"]["w_gate"],
                                  later["e_g"])
    # the two leaves whose layout differs between the two sides
    np.testing.assert_array_equal(p["layers"][2]["conv0_w"][:, 0],
                                  later["conv0"][0])
    np.testing.assert_array_equal(p["layers"][2]["conv1_w"][:, 1],
                                  later["conv1"][1])
    # the made-a-layer-at-a-time tree is `ref.init`'s
    whole = jax.jit(lambda s: ref.init(s, cfg))(jnp.uint32(5))
    assert jax.tree_util.tree_structure(whole) \
        == jax.tree_util.tree_structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(whole),
                            jax.tree_util.tree_leaves(params)):
        if "r_select_bias" in jax.tree_util.keystr(path):
            # evened out over rows that went through the layers below:
            # one program of all the layers rounds them otherwise
            np.testing.assert_allclose(a, b, atol=1e-6)
        else:
            np.testing.assert_array_equal(a, b)
    # the first layer has no residual to scale and no router state below
    assert "s_r" not in first["attn_merge"] and "r_gamma" not in first
    assert "s_r" in later["attn_merge"] and "r_gamma" in later
    # the spreads: a scale about 1, the rest about 0, none exactly there
    assert 0.05 < float(jnp.std(later["moe_merge"]["s_r"])) < 0.2
    assert float(jnp.abs(later["r_gamma"]).max()) > 0.3
    assert float(jnp.abs(later["tau"]).max()) > 0.05
    # what the program's own initialiser builds has the same shapes
    own = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda a: a.shape, own) \
        == jax.tree_util.tree_map(lambda a: a.shape, p)


def test_the_selection_bias_evens_out_the_rows_its_router_is_fed(toy):
    """The balancing tokens are a drawn prompt and answers that run from
    distinct tokens to long runs of one; sent through the made layers
    again, every layer's router gives each of its 17 outputs (the skip
    too) 1/17 of the answer rows, give or take a few ties, where without
    the bias one output gets twice that and another half."""
    cfg, params, _, _, _, _ = toy
    top = {k: v for k, v in params.items() if k != "layers"}
    toks = ref.balance_tokens(jnp.uint32(5), cfg)
    assert toks.shape == (8, 64) and int(toks.max()) < cfg["vocab_size"]
    runs = np.asarray(toks[:, 17:] == toks[:, 16:-1]).mean(-1)
    assert runs[0] < 0.05 and runs[-1] > 0.7
    assert np.asarray(toks[:, 1:16] == toks[:, :15]).mean() < 0.05
    r, y, rho = ref.balance_stream(top, toks)
    rows, outs = 8 * (64 - 16), np.arange(17)
    for lp in params["layers"]:
        r = ref._merged(lp["attn_merge"], r, y)
        y = jax.vmap(lambda u: ref.attention(lp, u, cfg))(
            ref._rms(r, lp["attn_norm"], 1e-5))
        r = ref._merged(lp["moe_merge"], r, y)
        u = ref._rms(r, lp["moe_norm"], 1e-5).reshape(512, -1)
        before = None if rho is None else rho.reshape(512, -1)
        _, p = ref.router(lp, u, before, cfg)
        answers = p.reshape(8, 64, 17)[:, 16:].reshape(rows, 17)
        got = np.asarray(jnp.argmax(answers + lp["r_select_bias"], -1))
        counts = (got[:, None] == outs).sum(0)
        assert np.abs(counts - rows // 17).max() <= 4, counts
        bare = (np.asarray(jnp.argmax(answers, -1))[:, None] == outs).sum(0)
        assert bare.max() > 2 * (rows // 17) and bare.min() <= rows // 34
        y, rho = ref.experts(lp, u, before, cfg)
        y, rho = y.reshape(r.shape), rho.reshape(8, 64, -1)


def test_full_forward_equals_the_reference(toy):
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(variables, toks)
    assert float(jnp.max(jnp.abs(got - want))) < TIGHT


@pytest.mark.parametrize("prompt_len", [1, 2, 5, 17, 32])
def test_prefill_then_decode_equals_the_references_full_forward(
        toy, prompt_len):
    """The rows and the slot's state come from the prefill, the logits from
    the decode step, which reads the state and rewrites it: both against
    the reference, which has neither. A prompt of ONE token leaves a state
    of zeros (z, a and u before position 0), a prompt of two the first
    position's."""
    _, _, model, variables, toks, want = toy
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(model, variables, toks[0], prompt_len)
    assert float(jnp.max(jnp.abs(got - want[0, prompt_len - 1:]))) < TIGHT


def test_the_engine_serves_what_the_reference_puts_first(toy):
    """Through `InferenceEngine` + `EngineRouter`, greedy, seven requests
    over three slots, so that requests join and leave while others decode
    (continuous batching over the state) and every slot is used again by a
    later request, one of them a prompt of one token: every served token
    is the reference's best at its position, to within TIGHT, and a
    released slot's state is zero."""
    from bigdl_tpu.serving import EngineRouter, InferenceEngine, Request

    cfg, params, model, variables, _, _ = toy
    engine = InferenceEngine(model, variables, slots=3, max_len=64,
                             prefill_buckets=(16, 32), block_size=4,
                             prefix_cache=False)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (5, 1, 30, 16, 9, 2, 12)]
    results = EngineRouter([engine]).run(
        [Request(prompt=p, max_new_tokens=m)
         for p, m in zip(prompts, (21, 7, 30, 11, 25, 16, 9))])
    assert [r.status for r in results] == ["done"] * len(prompts)
    assert engine.stats["prefill_calls"] == 7 > engine.slots
    with jax.default_matmul_precision("highest"):
        for p, r in zip(prompts, results):
            seq = jnp.asarray([(p + r.tokens)[:-1]])
            lg = ref.logits(params, seq, cfg)[0, len(p) - 1:]
            gap = jnp.max(lg, -1) - lg[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < TIGHT


# (what is left out, the line of the reference, what stands there instead)
ABLATIONS = [
    ("qk_mean_of_q", "q = c[:, :hq] + (q_lat + k_of_q) / 2",
     "q = c[:, :hq]"),
    ("qk_mean_of_k", "k = c[:, hq:] + (q_of_k + k_lat) / 2",
     "k = c[:, hq:]"),
    ("value_shift",
     "_previous(_mm(u, _f32(lp[\"w_v2\"]), precision))], -1",
     "_mm(u, _f32(lp[\"w_v2\"]), precision)], -1"),
    ("temperature",
     "k = dh ** 0.5 * jnp.exp(lp[\"tau\"])[None, :, None] * k \\",
     "k = dh ** 0.5 * k \\"),
    ("qk_norm",
     "q = dh ** 0.5 * q / jnp.linalg.norm(q, axis=-1, keepdims=True)",
     "pass"),
    ("depth_averaging",
     "rho = rho + lp[\"r_gamma\"] * rho_before", "pass"),
    ("the_skip",
     "p[:, :e], 0.0)", "p[:, :e], 0.0).at[:, 0].add("
     "jnp.where(chosen == e, p[:, e], 0.0))"),
    ("selection_bias",
     "chosen = jnp.argmax(p + lp[\"r_select_bias\"], axis=-1)",
     "chosen = jnp.argmax(p, axis=-1)"),
    ("the_weight_of_the_chosen", "p[:, :e], 0.0)", "1.0, 0.0)"),
    ("residual_scale",
     "new = (r + m[\"b_r\"]) * m[\"s_r\"] + new", "new = r + m[\"b_r\"] + new"),
    ("residual_bias", "new = (y + m[\"b_y\"]) * m[\"s_y\"]",
     "new = y * m[\"s_y\"]"),
    ("depthwise_previous_tap",
     "a = w0[0] * _previous(z) + w0[1] * z \\", "a = w0[1] * z \\"),
    ("grouped_previous_tap",
     "c = (_mm(_previous(a).transpose(1, 0, 2), w1[0], precision)\n"
     "         + _mm(a.transpose(1, 0, 2), w1[1], precision))",
     "c = (_mm(a.transpose(1, 0, 2), w1[1], precision))"),
    ("partial_rotation",
     "rot = int(dh * rope[\"partial_rotary_factor\"])", "rot = dh"),
    ("the_group_of_a_query_head",
     "k_of_q = jnp.repeat(k_lat, hq // g, axis=1)",
     "k_of_q = jnp.tile(k_lat, (1, hq // g, 1))"),
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
    copy = types.ModuleType(f"cca_moe_without_{name}")
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
                             fam.make_variables(5, low), toks[0], 17,
                             cache=jnp.bfloat16)
    assert 50 * TIGHT < float(jnp.max(jnp.abs(bf16 - want[0, 16:]))) < 1.0
    assert 500 * TIGHT < float(jnp.max(jnp.abs(fp8 - want))) < 4.0
    with pytest.raises(ValueError, match="precision"):
        ref.logits(params, toks, cfg, "bf16")


def test_counts_match_the_program_at_the_cells_configuration():
    from benchmarks.counts import cca_moe as counts

    with open(os.path.join(REPO, "benchmarks/configs/zaya1-8b.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["kept_layers"] == list(range(20))
    model = fam.program_model(cfg)
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(s.shape)) for s in leaves)
    ref_shapes = jax.eval_shape(lambda: ref.init(0, cfg))
    assert n == counts.params_held(cfg) == sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(ref_shapes))
    assert round(n / 1e6, 1) == 4688.8          # ISSUE 38: 20 x 207.59 + 537.13
    n32 = sum(int(np.prod(s.shape)) for s in leaves
              if s.dtype == jnp.float32)
    assert n32 == counts.float32_params_outside_experts(cfg)
    assert counts.expert_params(cfg) == 3 * 2048 * 2048
    assert counts.attention_matrix_params(cfg) \
        + counts.attention_float32_params(cfg) == 5_575_682   # "5.576M"
    assert counts.router_params(cfg, 1) == 661_009            # "0.661M"
    assert counts.slot_state_bytes(cfg) == model.slot_state_bytes() \
        == 20 * 2688 * 4
    # the whole 40-layer model: "8.4B" by its card, 8.84 by these keys
    whole = dict(cfg, num_hidden_layers=40)
    assert 8.3e9 < counts.params_held(whole) < 8.9e9
    # a decode step that touches every expert reads every weight once,
    # the float32 ones at four bytes
    full = counts.decode_bytes_per_step(cfg, 20 * 16, 0, 0)
    assert full == 2 * (n - n32) + 4 * n32
    none = counts.decode_bytes_per_step(cfg, 0, 0, 0)
    assert 1.3e9 < none < 1.4e9         # the head 1.07, twenty CCAs, routers
    # 1,024 B a cached position a layer; 215,040 B a seated slot
    assert counts.decode_bytes_per_step(cfg, 0, 1000, 0) - none \
        == 20 * 1000 * 1024
    assert counts.decode_bytes_per_step(cfg, 0, 0, 64) - none == 64 * 215_040


# ------------------------------------------------- the driver, end to end

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """benchmarks/ copied, the toy configuration and traffic laid beside
    the real files, and a manifest with one cell that reports what the
    real cell reports."""
    root = str(tmp_path_factory.mktemp("cca_moe_root"))
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
        "name": "tiny-cca-moe", "source": "none: a toy size",
        "file": "benchmarks/configs/tiny-cca-moe.json", "reduced": [],
        "why": "tests only"}], workloads=[{
            "name": CELL, "config": "tiny-cca-moe",
            "traffic": "tiny-reasoning-backlog", "chips": 1,
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
    assert {"cca_moe_decode_roofline", "moe_skip_share",
            "attn_rows_read_over_visible",
            "moe_expert_load_max_over_mean", "backlog_decode_step_p50",
            "backlog_queue_left", "backlog_peak_hbm"} <= names
    assert not {"moe_decode_roofline", "swa_moe_decode_roofline"} & names
    assert [w["chips"] for w in real["workloads"]].count(4) == 1
    assert len(real["workloads"]) == 7


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


def test_a_traced_run_reads_the_new_spans_and_both_readers_answer(
        tiny_root):
    """On the CPU there is no device trace, so the run leaves the roofline
    share out; what the spans carry is read, and the roofline's reader,
    handed the device's part (a main program's time and a peak), returns a
    share from the same spans."""
    from benchmarks.harness.runner import load_part
    from bigdl_tpu import obs

    root, _ = tiny_root
    result, lines = run_tiny(root, CELL, seed=13, trace=True)
    assert result["correct"] is True
    assert "cca_moe_decode_roofline" not in result["metrics"]
    load = result["metrics"]["moe_expert_load_max_over_mean"]["value"]
    assert 1.0 <= load <= 16.0          # 16 experts: at most all on one
    skip = result["metrics"]["moe_skip_share"]["value"]
    assert 0.0 <= skip < 50.0           # one output of seventeen
    assert 0 < result["metrics"]["backlog_queue_left"]["value"] < 100
    # every layer keeps all of a slot's rows: the read gathers whole
    # chunks of them, never fewer than the queries may see
    assert result["metrics"]["attn_rows_read_over_visible"]["value"] >= 1.0
    steps = [e for e in obs.get_tracer().events("decode_step")]
    assert all(e["args"]["window_rows"] == 0
               and e["args"]["full_rows"] == e["args"]["cached_tokens"]
               and e["args"]["attended_rows"] >= 3 * e["args"]["full_rows"]
               for e in steps)
    assert steps and all(
        len(e["args"]["experts_touched"]) == 3
        and len(e["args"]["skipped_rows"]) == 3
        # the program routes every row of the batch, seated or not
        and e["args"]["routed_rows"] == 3 * 4
        and max(e["args"]["experts_touched"]) <= 4 for e in steps)
    assert sum(sum(e["args"]["skipped_rows"]) for e in steps) > 0
    assert all(e["args"]["moe_assignments"] == e["args"]["bucket"]
               for e in obs.get_tracer().events("prefill"))
    # the roofline's reader over the same spans, with a device's part
    t0 = steps[0]["ts"] / 1e6
    t1 = (steps[-1]["ts"] + steps[-1]["dur"]) / 1e6
    ctx = types.SimpleNamespace(
        config=_cfg(), device={"platform": "tpu"}, out=lines.append,
        peaks={"hbm_bytes_per_s": 819e9},
        trace_summary={"devices": 1, "main_module": {
            "name": "jit__decode_step", "runs": len(steps),
            "time_s": 1e-3 * len(steps)}},
        trace_window=types.SimpleNamespace(begin_host=t0, end_host=t1),
        counts=lambda: load_part(root, "counts", "cca_moe"))
    share = load_part(root, "layer_metrics",
                      "cca_moe_decode_roofline").read(ctx)
    assert 0.0 < share < 100.0
    # a program that says nothing of skipped rows: nothing to read
    ctx.counts = lambda: load_part(root, "counts", "afmoe")
    assert load_part(root, "layer_metrics",
                     "cca_moe_decode_roofline").read(ctx) is None

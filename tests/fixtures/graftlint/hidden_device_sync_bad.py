# graftlint fixture: hidden-device-sync TRUE POSITIVES (judged as if
# at bigdl_tpu/serving/fixture.py — hot-path function names).
import jax
import numpy as np


def decode_step(logits, cache):
    tok = logits.item()  # BAD
    host = np.asarray(cache)  # BAD
    jax.device_get(logits)  # BAD
    logits.block_until_ready()  # BAD
    return tok, host


def observe_latency(registry, value):
    registry.observe(float(np.asarray(value)))  # BAD


# ISSUE 8: the paged-cache lookup/insert/evict/alloc paths are hot —
# block-table surgery runs between every decode step
def lookup_prefix(tree, tokens):
    return tree.walk(np.asarray(tokens))  # BAD


def evict_lru_block(pool, stamp_leaf):
    return stamp_leaf.item()  # BAD


def alloc_blocks(pool, n, stats):
    jax.device_get(stats)  # BAD
    return pool[:n]


def insert_chain(tree, blocks):
    blocks.block_until_ready()  # BAD
    return tree


# ISSUE 10: handoff export/import and pool placement are hot — a
# handoff moves once per request, placement runs on the step path
def import_handoff(pool, pkg):
    return np.asarray(pkg.kv)  # BAD


def place_pools(pools, stats):
    jax.device_get(stats)  # BAD
    return pools


# ISSUE 11: journey/flight-recorder paths run inside emit (an EventLog
# listener) — a sync there stalls the decode loop once per event
def build_journeys(events, loss):
    return [loss.item()]  # BAD


def dump_bundle(outdir, tail, gauge_leaf):
    return np.asarray(gauge_leaf)  # BAD


def record_event(ring, rec, value):
    ring.append(float(np.asarray(value)))  # BAD


# ISSUE 15: the speculative verify/rollback/mirror paths run between
# every draft-verify round — a stealth sync there stalls the whole
# batch once per round
def verify_round(nxt, finite):
    return np.asarray(nxt), finite.item()  # BAD


def rollback_slot(table, pos_leaf):
    return int(pos_leaf.item())  # BAD


def mirror_slot(draft_pool, pkg):
    return jax.device_get(draft_pool)  # BAD


# ISSUE 16: the host spill tier's spill/readmit/migrate paths run
# between decode steps (eviction cascade, prefix re-admission, trip-
# time tree migration) — only the export's ONE batched fetch may sync
def spill_victims(pool, victims, stamps):
    order = np.asarray(stamps)  # BAD
    return [pool[v] for v in victims], order


def readmit_chain(host_blocks, table, occupancy_leaf):
    jax.device_get(occupancy_leaf)  # BAD
    return table


def migrate_tree(entries, survivor, depth_leaf):
    return survivor.graft(entries, depth_leaf.item())  # BAD


# ISSUE 17: quant/repack paths — quantization runs once at engine
# construction, but a fetch inside the repack pulls the whole fp32
# tree to the host leaf by leaf
def quantize_serving_params(params):
    return {k: np.asarray(v) for k, v in params.items()}  # BAD


def repack_weight(w, scale_leaf):
    return w, scale_leaf.item()  # BAD


# ISSUE 18 speculation flywheel: swap/distill/adapt paths run BETWEEN
# decode rounds on a LIVE engine — the hot-swap is re-placement over
# tree metadata and the k ladder is host arithmetic; any fetch here
# stalls serving once per swap or per evaluation
def swap_params(engine, variables):
    return np.asarray(variables["params"]["embed"])  # BAD


def swap_draft(spec, leaves):
    return [leaf.item() for leaf in leaves]  # BAD


def distill_round(corpus, params_leaf):
    return corpus, jax.device_get(params_leaf)  # BAD


def adapt_lookahead(window_leaf, k_live):
    return min(k_live, int(window_leaf.item()))  # BAD

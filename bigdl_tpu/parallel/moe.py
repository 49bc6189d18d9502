"""Mixture-of-Experts with expert parallelism over a mesh axis.

No reference counterpart (SURVEY.md §2.3 lists EP as absent); this is
the `expert` mesh axis. Switch-style top-1 routing with capacity:

    gates   = softmax(x @ router)                 (T, E)
    expert  = argmax(gates); position-in-expert via cumsum
    dispatch = onehot(expert) ∧ (position < capacity)   (T, E, C)
    expert_in  = dispatchᵀ x                      (E, C, D)
    --- all_to_all over the expert axis ---       each device receives
    expert_out = local experts (E_local of them)  every device's tokens
    --- all_to_all back ---                       for ITS experts
    y = combine (dispatch · gate) expert_out      (T, D)

The einsum-dispatch formulation keeps everything dense/static for XLA
(no dynamic shapes — dropped tokens beyond capacity fall out of the
dispatch mask, the standard Switch trade-off) and the two all_to_alls
are the only cross-device traffic, riding ICI.

A load-balancing auxiliary loss (mean gate fraction × mean dispatch
fraction × E, per Switch/GShard) is returned alongside the output.

`DroplessMoE`, further down, is the other family of expert layer:
top-k of many experts by a sigmoid router with a selection bias, a
shared expert, no capacity and no dropped token (the DeepSeek-V3
line of models). It is the serving FFN of models/latent_moe.py.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module
from bigdl_tpu.ops import grouped_matmul as _gm


class MoE(Module):
    """Top-1 (Switch) MoE feed-forward layer.

    apply(variables, x (..., T, D)) → ((..., T, D), aux_loss) — the
    output is a tuple; aux_loss should be added to the training loss
    scaled by e.g. 0.01.

    With `expert_axis` set, apply() must run inside shard_map on a mesh
    containing that axis; the expert-stacked params (leading dim
    num_experts) are then sharded P(expert_axis, ...) and each device
    holds num_experts/axis_size experts, exchanging tokens via
    all_to_all.
    """

    def __init__(self, dim: int, hidden: int, num_experts: int,
                 capacity_factor: float = 1.25,
                 expert_axis: Optional[str] = None, top_k: int = 1,
                 routing: str = "top_k",
                 name: Optional[str] = None):
        super().__init__(name=name)
        if top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {top_k}")
        if routing not in ("top_k", "expert_choice"):
            raise ValueError(
                f"routing must be top_k|expert_choice, got {routing!r}")
        if routing == "expert_choice" and top_k != 1:
            raise ValueError(
                "top_k has no meaning under expert_choice routing "
                "(experts pick tokens; capacity_factor is the knob) — "
                "leave top_k=1")
        self.dim = dim
        self.hidden = hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        self.top_k = top_k
        self.routing = routing

    def init_params(self, rng):
        e, d, f = self.num_experts, self.dim, self.hidden
        ks = jax.random.split(rng, 3)
        init = Xavier()
        return {
            "router": init(ks[0], (d, e), fan_in=d, fan_out=e),
            "w1": init(ks[1], (e, d, f), fan_in=d, fan_out=f),
            "b1": jnp.zeros((e, f), jnp.float32),
            "w2": init(ks[2], (e, f, d), fan_in=f, fan_out=d),
            "b2": jnp.zeros((e, d), jnp.float32),
        }

    def _route(self, x2, router):
        """x2: (T, D) → dispatch (T, E, C), combine (T, E, C), aux.

        top_k=1: Switch. top_k=2: GShard — second choice masked from the
        first, both gate values renormalized to sum to 1, second-choice
        tokens queue BEHIND all first-choice tokens in an expert's
        capacity buffer (first choices are never dropped in favor of
        seconds). Capacity scales with top_k.
        """
        t = x2.shape[0]
        e = self.num_experts
        cap = max(1, int(self.capacity_factor * self.top_k * t / e))
        gates = jax.nn.softmax(x2 @ router, axis=-1)          # (T, E)

        def choice_slot(onehot, offset):
            """dispatch mask (T,E,C) for one choice, given per-expert
            queue offsets (E,) from earlier choices."""
            pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0   # (T, E)
            pos = pos + offset[None, :] * onehot
            keep = onehot * (pos < cap)                       # (T, E)
            pos_oh = jax.nn.one_hot(
                pos.max(axis=-1).astype(jnp.int32), cap,
                dtype=jnp.float32)                            # (T, C)
            return keep[:, :, None] * pos_oh[:, None, :], keep

        oh1 = jax.nn.one_hot(jnp.argmax(gates, axis=-1), e,
                             dtype=jnp.float32)               # (T, E)
        d1, keep1 = choice_slot(oh1, jnp.zeros((e,), jnp.float32))
        g1 = jnp.sum(gates * keep1, axis=-1)                  # (T,)

        # Switch load-balancing aux from the FIRST choice (both modes):
        # fraction routed × mean gate, per expert
        frac = jnp.mean(oh1, axis=0)
        mean_gate = jnp.mean(gates, axis=0)
        aux = jnp.sum(frac * mean_gate) * e

        if self.top_k == 1:
            combine = d1 * g1[:, None, None]
            return d1, combine, aux, cap

        gates2 = gates * (1.0 - oh1)                          # mask top-1
        oh2 = jax.nn.one_hot(jnp.argmax(gates2, axis=-1), e,
                             dtype=jnp.float32)
        d2, keep2 = choice_slot(oh2, jnp.sum(oh1, axis=0))
        g2 = jnp.sum(gates * keep2, axis=-1)
        # renormalize over the SURVIVING choices (a dropped second
        # choice leaves the first at full weight, and vice versa)
        denom = g1 + g2 + 1e-9
        w1, w2 = g1 / denom, g2 / denom
        dispatch = d1 + d2          # disjoint experts: no overlap
        combine = d1 * w1[:, None, None] + d2 * w2[:, None, None]
        return dispatch, combine, aux, cap

    def _route_expert_choice(self, x2, router):
        """Expert-choice routing (Zhou et al. 2022) — the dropless
        answer to Switch's capacity dropping: instead of tokens picking
        experts (and overflowing their buffers), each EXPERT picks its
        top-C tokens by affinity. Every expert buffer is exactly full —
        perfect load balance BY CONSTRUCTION, so there is no capacity
        overflow, no dropped-token path, and no load-balancing
        auxiliary loss (aux ≡ 0).

        Static shapes throughout: `lax.top_k` over the token axis per
        expert, dense one-hot dispatch — the same (T, E, C) dispatch /
        combine tensors the top-k router emits, so the expert-parallel
        all_to_all plumbing is shared unchanged.

        Caveat (documented, inherent to the method): expert selections
        depend on ALL tokens in the batch/sequence, so it is not
        causally masked — use for encoder-style models, or accept the
        train-time approximation for decoder LMs.
        """
        t = x2.shape[0]
        e = self.num_experts
        cap = max(1, min(t, int(self.capacity_factor * t / e)))
        scores = jax.nn.softmax(x2 @ router, axis=-1)         # (T, E)
        g, idx = lax.top_k(scores.T, cap)                     # (E, C)
        # dispatch[t, e, c] = 1 iff expert e picked token t for slot c
        dispatch = jax.nn.one_hot(idx, t, dtype=jnp.float32,
                                  axis=-1).transpose(2, 0, 1)  # (T,E,C)
        combine = dispatch * g[None, :, :]    # affinity as gate weight
        return dispatch, combine, cap

    def _experts(self, p, xin):
        """xin: (E_local, C_tot, D) → same shape through each expert."""
        h = jnp.einsum("ecd,edf->ecf", xin, p["w1"]) + p["b1"][:, None, :]
        h = jax.nn.gelu(h)
        return jnp.einsum("ecf,efd->ecd", h, p["w2"]) + p["b2"][:, None, :]

    def apply(self, variables, x, training=False, rng=None):
        p = variables["params"]
        shape = x.shape
        x2 = x.reshape(-1, self.dim)
        if self.routing == "expert_choice":
            dispatch, combine, cap = self._route_expert_choice(
                x2, p["router"])
            aux = jnp.zeros((), jnp.float32)  # balanced by construction
        else:
            dispatch, combine, aux, cap = self._route(x2, p["router"])

        if self.expert_axis is None:
            xin = jnp.einsum("tec,td->ecd", dispatch, x2)
            yout = self._experts(p, xin)
            y = jnp.einsum("tec,ecd->td", combine, yout)
            return (y.reshape(shape), aux), variables["state"]

        # expert-parallel: params arrive expert-sharded; route globally,
        # exchange tokens so each device runs only its local experts
        axis = self.expert_axis
        n = lax.axis_size(axis)
        e_local = p["w1"].shape[0]                 # num_experts / n
        if e_local * n != self.num_experts:
            raise ValueError(
                f"num_experts {self.num_experts} != {e_local}·{n}")
        xin = jnp.einsum("tec,td->ecd", dispatch, x2)   # (E, C, D)
        # (E, C, D) = (n, e_local, C, D): send slice j to device j
        xin = xin.reshape(n, e_local, cap, self.dim)
        xin = lax.all_to_all(xin, axis, split_axis=0, concat_axis=0,
                             tiled=True)               # (n, e_local, C, D)
        xin = xin.transpose(1, 0, 2, 3).reshape(
            e_local, n * cap, self.dim)                # my experts, all toks
        yout = self._experts(p, xin)                   # (e_local, nC, D)
        yout = yout.reshape(e_local, n, cap, self.dim).transpose(1, 0, 2, 3)
        yout = lax.all_to_all(yout, axis, split_axis=0, concat_axis=0,
                              tiled=True)              # (n, e_local, C, D)
        yout = yout.reshape(self.num_experts, cap, self.dim)
        y = jnp.einsum("tec,ecd->td", combine, yout)
        # aux is computed from THIS shard's tokens only — callers must
        # pmean it over the expert axis before using it as a loss term
        return (y.reshape(shape), aux), variables["state"]


class DroplessMoE(Module):
    """Dropless top-k expert layer with a sigmoid router, SiLU-gated
    experts and a shared expert (DeepSeek-V3's `MoE` with
    `topk_method: noaux_tc`, `scoring_func: sigmoid`, one group):

        s      = sigmoid(x W_r)                      (T, E), float32
        chosen = top_k(s + b)          b selects and does not weigh
        w_i    = scale * s_i / sum_chosen s_j        (norm_topk_prob)
        y      = sum_i w_i E_i(x) + E_shared(x)
        E(x)   = (silu(x W_g) * x W_u) W_d

    No capacity, no (T, E, C) tensor, no auxiliary loss: the T * k
    assignments are sorted by expert and each of the three expert
    matmuls is ONE grouped matmul over the sorted rows with the
    per-expert counts as group sizes (ops/grouped_matmul.py), which
    reads only the experts with rows. Its form goes by the platform
    and the static shapes and by nothing else: on a TPU, for bfloat16
    experts whose matrix is at most 4 MiB (JoyAI's, Trinity's), a
    Pallas kernel that streams each touched expert's matrix once, at
    decode and at prefill; everywhere else (the CPU, float32, zaya's
    8 MB matrices) `lax.ragged_dot`, which the TPU compiler turns into
    its own grouped matmul. The break-even and the chip table that set
    it are at `ops/grouped_matmul._STREAM_MATRIX_BYTES`;
    `expert_matmul` says which form a program of so many tokens
    takes. The same `forward` serves 64 decode rows and a 2,048-token
    prefill; every shape is static.
    `forward` also returns the tokens each expert got (int32[E]),
    which is free here and what the serving engine's `aux` carries
    (serving/engine.py).

    Expert weights may be bfloat16; products accumulate in float32.
    The router runs in float32 at the highest matmul precision: a
    top-k over 256 near-equal scores is decided in the fourth digit.
    Single-mesh: an expert layer that is told which experts it holds,
    and its exchange, are ROADMAP B-I 3.
    """

    def __init__(self, dim: int, hidden: int, num_experts: int,
                 top_k: int, shared_hidden: int = 0,
                 scale: float = 1.0, normalize: bool = True,
                 name: Optional[str] = None):
        super().__init__(name=name)
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.dim, self.hidden, self.num_experts = dim, hidden, num_experts
        self.top_k, self.shared_hidden = top_k, shared_hidden
        self.scale, self.normalize = scale, normalize

    def init_params(self, rng):
        e, d, f, fs = (self.num_experts, self.dim, self.hidden,
                       self.shared_hidden)
        ks = jax.random.split(rng, 7)
        init = Xavier()
        p = {
            "router": init(ks[0], (d, e), fan_in=d, fan_out=e),
            "router_bias": jnp.zeros((e,), jnp.float32),
            "w_gate": init(ks[1], (e, d, f), fan_in=d, fan_out=f),
            "w_up": init(ks[2], (e, d, f), fan_in=d, fan_out=f),
            "w_down": init(ks[3], (e, f, d), fan_in=f, fan_out=d),
        }
        if fs:
            p.update(ws_gate=init(ks[4], (d, fs), fan_in=d, fan_out=fs),
                     ws_up=init(ks[5], (d, fs), fan_in=d, fan_out=fs),
                     ws_down=init(ks[6], (fs, d), fan_in=fs, fan_out=d))
        return p

    def route(self, p, x32):
        """x32 (T, D) float32 → chosen experts (T, k) int32 and their
        weights (T, k) float32."""
        logits = jnp.dot(x32, p["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(s + p["router_bias"], self.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if self.normalize:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return idx, w * self.scale

    def forward(self, p, x, x32=None, routing=None):
        """x (T, D) in the experts' compute dtype (x32: the same rows
        in float32 for the router, where the caller has them) →
        (y (T, D) float32, tokens per expert int32[E]).

        `routing` is a routing the MODEL decided, in place of `route`:
        (experts (T, k) int32, weights (T, k) float32), whatever its
        router is (models/cca_moe.py: an MLP's softmax, top-1). The id
        `num_experts` there sends an assignment to NO expert: it sorts
        behind every expert's rows, lies in no group of the grouped
        matmuls (which read no expert for it), adds nothing to `y`,
        and is counted in a last column of its own, int32[E + 1]."""
        t, k, e = x.shape[0], self.top_k, self.num_experts
        if routing is None:
            idx, w = self.route(p, x.astype(jnp.float32) if x32 is None
                                else x32)
            bins = e
        else:
            idx, w = routing
            bins = e + 1
        flat = idx.reshape(t * k)
        order = jnp.argsort(flat)             # stable: ties by token
        counts = jnp.zeros((bins,), jnp.int32).at[flat].add(1)
        sizes = counts if bins == e else counts[:e]
        xs = x[order // k]                    # (T*k, D), expert-sorted

        def grouped(a, wgt):
            return _gm.grouped_matmul(a, wgt, sizes)

        h = jax.nn.silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
        ys = grouped(h.astype(x.dtype), p["w_down"])      # (T*k, D)
        if bins != e:
            # rows past the last group: whatever the grouped matmul
            # leaves there is not a result
            ys = jnp.where((flat[order] < e)[:, None], ys, 0.0)
        # back to (token, choice) order, then the weighted sum over a
        # token's k experts in a fixed order (no scatter-add)
        y = ys[jnp.argsort(order)].reshape(t, k, self.dim)
        y = jnp.sum(y * w[:, :, None], axis=1)
        if self.shared_hidden:
            y = y + gated_ffn(x, p["ws_gate"], p["ws_up"], p["ws_down"])
        return y, counts

    def expert_matmul(self, p, tokens: int) -> str:
        """The form `forward`'s three grouped matmuls take for `tokens`
        rows of x, "stream" or "ragged_dot" (ops/grouped_matmul
        .grouped_matmul_form): static per compiled program, the serving
        engine's `expert_matmul` label."""
        return _gm.grouped_matmul_form(
            tokens * self.top_k, self.num_experts, self.dim, self.hidden,
            p["w_gate"].dtype)

    def apply(self, variables, x, training=False, rng=None):
        p = variables["params"]
        y, _ = self.forward(p, x.reshape(-1, self.dim))
        return y.reshape(x.shape).astype(x.dtype), variables["state"]


def expert_load_report(aux, skip_column: bool = False):
    """From one decode step's fetched `aux` (MoE layers, E), the tokens
    each expert got (`DroplessMoE.forward`'s second result, stacked by
    the model): the args the serving engine hangs on its `decode_step`
    span, what a model's `decode_aux_report` returns.
    `moe_assignments` is the rows that reached an expert, summed over
    the layers (the `prefill` span's word for the same count). With
    `skip_column` the aux is (MoE layers, E + 1), its last column the
    rows that went to no expert (a routing the model decided):
    `skipped_rows` a layer, and `routed_rows`, all the rows the
    layers' routers placed."""
    import numpy as np

    aux = np.asarray(aux)
    args = {}
    if skip_column:
        args = {"skipped_rows": [int(n) for n in aux[:, -1]],
                "routed_rows": int(aux.sum())}
        aux = aux[:, :-1]
    mean = np.maximum(aux.mean(axis=1), 1e-9)
    return {"experts_touched": [int(n) for n in (aux > 0).sum(1)],
            "expert_load_max_over_mean": [
                float(v) for v in aux.max(axis=1) / mean],
            "moe_assignments": int(aux.sum()), **args}


class ExpertsReport:
    """What the experts of a served model tell the engine's spans
    (serving/protocol.py, "what the spans say"): a mixin for a model
    whose expert sublayers are `self.moe`, a `DroplessMoE`, listed
    before `ServedModel` among its bases."""

    # the key of a layer's parameters that holds its experts' weights
    experts_key = "moe"
    # the aux's last column is the rows a router sent to no expert
    # (`expert_load_report`'s `skip_column`)
    aux_skip_column = False

    def decode_aux_report(self, aux) -> dict:
        return expert_load_report(aux, skip_column=self.aux_skip_column)

    def expert_matmul_form(self, params, tokens: int) -> str:
        """The engine's `expert_matmul` label for a program of
        `tokens` rows (`DroplessMoE.expert_matmul`)."""
        experts = next(lp[self.experts_key] for lp in params["layers"]
                       if self.experts_key in lp)
        return self.moe.expert_matmul(experts, tokens)

    def prefill_span_args(self, bucket: int) -> dict:
        return {"moe_assignments": bucket * self.moe.top_k}


def gated_ffn(x, w_gate, w_up, w_down):
    """(silu(x W_g) * x W_u) W_d with float32 accumulation; the hidden
    activation goes back to x's dtype between the matmuls."""
    def mm(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    h = jax.nn.silu(mm(x, w_gate)) * mm(x, w_up)
    return mm(h.astype(x.dtype), w_down)


def moe_specs(expert_axis: str = "expert"):
    """PartitionSpecs for MoE params (experts stacked on the lead dim)."""
    from jax.sharding import PartitionSpec as P

    return {"router": P(),
            "w1": P(expert_axis, None, None), "b1": P(expert_axis, None),
            "w2": P(expert_axis, None, None), "b2": P(expert_axis, None)}


def moe_lm_specs(ep_axis: str, tie_embeddings: bool = True):
    """PartitionSpecs for a MoE-FFN TransformerLM's params: expert-
    stacked block leaves (l, EX, ...) sharded on the EXPERT dim, all
    else replicated. Derived from transformer_tp_specs (param-key
    structure) + moe_specs (expert leaf layout) so there is no third
    hand-maintained key list."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.parallel.tensor_parallel import transformer_tp_specs

    base = transformer_tp_specs("unused_axis", tie_embeddings)
    specs = jax.tree_util.tree_map(
        lambda _: P(), base, is_leaf=lambda x: isinstance(x, P))
    # MoE leaves: moe_specs' per-expert layout with the layer dim
    # prepended; the replicated router stays P()
    specs["blocks"].update({
        k: (P() if k == "router" else P(None, *tuple(s)))
        for k, s in moe_specs(ep_axis).items()})
    return specs


def make_moe_lm_train_step(model, method, mesh, ep_axis: str = "expert"):
    """Jitted expert-parallel training step for a MoE-FFN TransformerLM.

    Signature: (params, slots, tokens, targets, lr, stepno, rng)
             -> (params', slots', mean_loss)

    The expert axis doubles as the batch axis (tokens shard on it, the
    standard EP deployment): each device computes its shard's loss with
    the per-layer all_to_all expert exchange inside the scan. Scaling:
    the local loss is the local token-mean divided by the axis size, so
    summed over shards it is the GLOBAL mean — expert-sharded leaves'
    gradients then arrive complete and correctly scaled through the
    all_to_all transposes with no extra collective, while replicated
    leaves (router, attention, embeddings) psum their per-shard
    contributions. The model must be built with ep_axis=<axis>.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if getattr(model, "ep_axis", None) != ep_axis:
        raise ValueError(
            f"model.ep_axis={getattr(model, 'ep_axis', None)!r} != "
            f"step ep_axis={ep_axis!r}")
    if model.tp_axis is not None or model.sp_axis is not None:
        raise NotImplementedError(
            "the EP step runs on a pure expert mesh (the expert axis "
            "doubles as the batch axis); tp/sp composition is not "
            "implemented")
    n = mesh.shape[ep_axis]
    specs = moe_lm_specs(ep_axis, model.cfg.tie_embeddings)

    def body(params, slots, tokens, targets, lr, stepno, rng):
        rng = jax.random.fold_in(rng, lax.axis_index(ep_axis))

        def loss_fn(p):
            # local token-mean / n: sums to the global mean over shards
            return model.loss({"params": p, "state": {}}, tokens,
                              targets, training=True, rng=rng) / n

        local_loss, grads = jax.value_and_grad(loss_fn)(params)

        # replicated leaves: per-shard partial contributions → psum;
        # expert-sharded leaves: already complete via the all_to_all
        # transposes
        grads = jax.tree_util.tree_map(
            lambda sp, g: g if any(a is not None for a in sp)
            else lax.psum(g, ep_axis),
            specs, grads, is_leaf=lambda x: isinstance(x, P))
        loss = lax.psum(local_loss, ep_axis)

        new_params, new_slots = method.update(grads, params, slots, lr,
                                              stepno)
        return new_params, new_slots, loss

    from bigdl_tpu.parallel.tensor_parallel import slot_specs_for

    slot_specs = slot_specs_for(method, specs)
    tok_spec = P(ep_axis, None)
    smapped = shard_map(
        body, mesh=mesh,
        in_specs=(specs, slot_specs, tok_spec, tok_spec, P(), P(), P()),
        out_specs=(specs, slot_specs, P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0, 1))

"""Online draft distillation — the training half of the speculation
flywheel (ISSUE 18).

The accept rate of a `SpeculativeEngine` is exactly how well the draft
predicts the TARGET's next sample on the traffic actually being
served. That makes the fleet's own emitted token streams the ideal
distillation corpus: every result the target produced is, verbatim, a
(context -> next-token) supervision signal for the draft.
`DraftDistiller` closes the loop:

    distiller = DraftDistiller(spec.draft_engine.model)
    for res in results:
        distiller.ingest(res)            # prompt + emitted tokens
    spec.swap_draft(distiller.distill()) # hot-swap, zero compiles

`distill()` trains FROM the draft's current weights (warm start — the
flywheel accumulates) on a ZeRO-2 `Optimizer` loop (`set_mesh(mesh,
zero=2)`; a 1-device mesh by default, so the background loop works on
a single host exactly like the elastic-training plane's, ISSUE 9) and
returns a FRESH variables pytree for `SpeculativeEngine.swap_draft` /
`InferenceEngine.swap_params`. The serving side never notices the
training: the model object's live variables are restored after the
run, the returned tree shares no buffers with the serving layout, and
the swap itself is pure re-placement over the param-layout spine —
zero new executables. Tokens cannot move either way: acceptance is
coupled sampling (serving/speculative.py), so a better draft raises
ONLY the accept rate.

Determinism: ingestion order is the sample order, the Optimizer seed
is a constructor arg, and training runs on the repo's deterministic
step — two distills over the same streams return bitwise-identical
variables, which is what lets the spec_adapt drill pin byte-identical
reports across runs.

All knobs are CONSTRUCTOR args, never env (graftlint trace-env-read).
"""

from __future__ import annotations

from collections import deque
from typing import Deque, List, Optional

import numpy as np


class DraftDistiller:
    """Accumulate served token streams; train an improved draft.

    `model` is the draft's model object (e.g.
    `spec.draft_engine.model`); its `cfg.max_len` must cover
    `seq_len`. Streams shorter than seq_len+1 tokens are skipped —
    windows must share one shape so the training step compiles once.
    """

    def __init__(self, model, *, seq_len: int = 16, batch_size: int = 32,
                 learningrate: float = 3e-3, epochs: int = 2,
                 zero: int = 2, mesh=None, max_streams: int = 1024,
                 seed: int = 0):
        if seq_len < 1:
            raise ValueError("seq_len must be >= 1")
        max_len = getattr(getattr(model, "cfg", None), "max_len", None)
        if max_len is not None and seq_len > max_len:
            raise ValueError(f"seq_len {seq_len} exceeds the draft's "
                             f"max_len {max_len}")
        if epochs < 1:
            raise ValueError("epochs must be >= 1")
        if zero not in (1, 2):
            raise ValueError(f"zero must be 1 or 2, got {zero!r}")
        self._model = model
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self.learningrate = float(learningrate)
        self.epochs = int(epochs)
        self.zero = int(zero)
        self.mesh = mesh
        self.seed = int(seed)
        # newest-wins corpus bound: the flywheel should chase CURRENT
        # traffic, so old streams age out first
        self._streams: Deque[List[int]] = deque(maxlen=int(max_streams))
        self._distills = 0

    # ---------------------------------------------------------- corpus
    def ingest(self, stream) -> int:
        """Add one served stream: a `GenerationResult` (prompt +
        emitted tokens — the target-only sequence verbatim) or a raw
        token iterable. Returns the number of training windows the
        corpus now yields from it."""
        if hasattr(stream, "tokens") and hasattr(stream, "prompt"):
            toks = [int(x) for x in stream.prompt] \
                + [int(x) for x in stream.tokens]
        else:
            toks = [int(x) for x in stream]
        self._streams.append(toks)
        return len(self._windows(toks))

    @property
    def streams(self) -> int:
        return len(self._streams)

    @property
    def distills(self) -> int:
        return self._distills

    def _windows(self, toks: List[int]) -> List[np.ndarray]:
        """Fixed-shape (seq_len+1) windows over one stream: stride
        seq_len, plus one end-anchored window so the stream's tail
        (the freshest target behavior) is never dropped."""
        L = self.seq_len
        n = len(toks)
        if n < L + 1:
            return []
        starts = list(range(0, n - L, L))
        if starts[-1] != n - L - 1:
            starts.append(n - L - 1)
        return [np.asarray(toks[s0:s0 + L + 1], np.int32)
                for s0 in starts]

    def _samples(self):
        from bigdl_tpu.dataset.sample import Sample

        out = []
        for toks in self._streams:
            for w in self._windows(toks):
                out.append(Sample(w[:-1], w[1:]))
        return out

    # ----------------------------------------------------------- train
    def distill(self):
        """One distillation round: warm-start from the model's current
        variables, train on every ingested window, return a fresh
        variables pytree for `swap_draft`. On success the model
        object's variables ADVANCE to the distilled weights (the
        flywheel accumulates — the next round warm-starts from here);
        on failure they are restored untouched. Live engines never
        notice either way: their serving layout snapshots variables at
        construction/swap time, not through the model object."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu import nn
        from bigdl_tpu.dataset import DataSet
        from bigdl_tpu.optim import Adam, Optimizer, Trigger

        samples = self._samples()
        if not samples:
            raise RuntimeError(
                "distill() with an empty corpus: ingest() at least one "
                f"stream of >= seq_len+1 (= {self.seq_len + 1}) tokens "
                "first")
        model = self._model
        prev = model.variables
        # train on COPIES: the step donates/updates its buffers, and
        # the serving engine's layout must never alias training state
        model.variables = jax.tree_util.tree_map(jnp.array, prev)
        ok = False
        try:
            opt = (Optimizer(model, DataSet.array(samples),
                             nn.ChunkedSoftmaxCE(),
                             batch_size=min(self.batch_size,
                                            len(samples)),
                             seed=self.seed)
                   .set_optim_method(Adam(learningrate=self.learningrate))
                   .set_end_when(Trigger.max_epoch(self.epochs)))
            mesh = self.mesh
            if mesh is None:
                # the background-loop default: a 1-device mesh keeps
                # the ZeRO-2 path (flat master shards, ISSUE 9)
                # without contending for the serving devices
                # device HANDLES into a mesh grid — no array data
                # leaves a device here
                mesh = jax.sharding.Mesh(
                    np.asarray(jax.devices()[:1]), ("data",))  # graftlint: disable=hidden-device-sync
            opt.set_mesh(mesh, zero=self.zero)
            opt.optimize()
            new_vars = model.variables
            ok = True
        finally:
            if not ok:
                model.variables = prev
        self._distills += 1
        return new_vars

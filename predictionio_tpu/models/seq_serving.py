"""The one serving route of the sequence models (models/seq_attention.py,
models/looped_lm.py): ``RetrievalServingMixin`` over a catalog that is
the model's output table, and a ``ServingPipeline`` whose query table is
the int32 history table and whose encoder is the model's forward.

    users -> rows -> histories[rows] -> encoder(params, .) -> the last
    position's state [B, D] -> the retriever's fused top-k

No ``[B, vocab]`` array crosses to the host. ``exclude_seen`` over-fetches
``num`` + the history's distinct items on a k lattice and drops the seen
where the answers are assembled.
"""

from __future__ import annotations

import numpy as np

from ..obs.startup import STARTUP
from ..obs.trace import span
from ..ops.retrieval import RetrievalServingMixin

__all__ = ["SequenceServingMixin", "k_lattice"]


def k_lattice(max_len: int) -> tuple[int, ...]:
    """The k's a sequence model's head is compiled for: 16, 64, 256, ...
    below ``max_len``, then ``max_len`` + 16 (a default ``num`` on top of
    a full history of distinct items)."""
    ks, k = [], 16
    while k < max_len:
        ks.append(k)
        k *= 4
    return tuple(ks) + (max_len + 16,)


class SequenceServingMixin(RetrievalServingMixin):
    """For a model with ``seqs`` [users, max_len] (left-padded, 0 = pad,
    item i stored as i + 1), ``user_ids``, ``item_ids``, a ``catalog``
    property ([items, D] float32, the pad row left out) and
    ``make_encoder()`` (the contract is in ops/pipeline.py); optionally
    ``serving_ks``, the k's its head is compiled for (default
    ``k_lattice`` of the history length: ``exclude_seen``'s over-fetch)."""

    _retrieval_attr = "catalog"
    _query_attr = "seqs"

    def attach_pipeline(self) -> None:
        from ..ops.pipeline import ServingPipeline

        with span("deploy.attach_encoder", sink=STARTUP.phase,
                  model=type(self).__name__) as s:
            encoder = self.make_encoder()
            s["bytes"] = int(getattr(encoder, "param_bytes", 0))
        self._pipeline = ServingPipeline(
            self.seqs, getattr(self, "_retriever", None), encoder=encoder,
            ks=(getattr(self, "serving_ks", None)
                or k_lattice(self.seqs.shape[1])))

    def _serving_pipeline(self):
        """The attached pipeline; a model nobody deployed (a test, a
        library caller, ``pio eval``) attaches its own on first use, so
        that there is one route and not a host twin of it."""
        if getattr(self, "_pipeline", None) is None:
            if getattr(self, "_retriever", None) is None:
                self.attach_retriever()
            self.attach_pipeline()
        return self._pipeline

    # -- what the micro-batcher's cut reads ------------------------------
    @property
    def serving_cost_budget(self) -> int:
        return self._serving_pipeline().cost_budget

    def serving_cost(self, user) -> int:
        """Tokens the user's query adds to a step (0: answered empty)."""
        row = self.user_ids.get(user)
        return 0 if row is None else self._serving_pipeline().row_cost(row)

    # -- answers -----------------------------------------------------------
    def recommend_products(self, user_id: str, num: int, *,
                           exclude_seen: bool = True
                           ) -> list[tuple[str, float]]:
        return self.batch_recommend([user_id], [num],
                                    exclude_seen=exclude_seen)[0]

    def batch_recommend(self, users: list, nums: list, *,
                        exclude_seen: bool = True
                        ) -> list[list[tuple[str, float]]]:
        """Per-user next-item top-N through the pipeline; unknown users
        and users without a single event get []."""
        out: list = [[] for _ in users]
        pipe = self._serving_pipeline()
        rows = self.user_ids.map_array(users)
        known = np.flatnonzero(rows >= 0)
        known = known[pipe.history_lengths(rows[known]) > 0]
        if known.size == 0:
            return out
        kmax = max(max(nums[j] for j in known), 0)
        if kmax <= 0:
            return out
        k, seen = kmax, [None] * known.size
        if exclude_seen:
            seen = [np.unique(h[h > 0]) - 1 for h in self.seqs[rows[known]]]
            k += max(len(s) for s in seen)
            k = next((lat for lat in pipe.ks if lat >= k), k)
        vals, idx = pipe.topk_rows(rows[known], k)
        inv = self._catalog_ids_inverse()
        for j, vr, ir, s in zip(known.tolist(), vals, idx, seen):
            keep = ir >= 0
            if s is not None:
                keep &= ~np.isin(ir, s)
            num = max(nums[j], 0)
            out[j] = [(inv[int(i)], float(v))
                      for v, i in zip(vr[keep][:num], ir[keep][:num])]
        return out

"""``engine.topk(k, X=batch)``: each held-out row's k nearest training rows
by proximity, values descending.  Compared: ``topk_gap``.

For each sampled row and each rank r < k, the larger of |returned value −
the reference's r-th largest proximity| and |the reference's proximity at
the returned index − returned value|, over the row's largest reference
proximity; the largest over rows and ranks.  Values only, so ties at rank
k (the rule where a proximity counts shared trees) are no trouble.
Repeated or out-of-range indices, a wrong shape or a non-finite value read
inf.

The reference is the dense proximity row in float64 from the reference's
own routing and weights, a sparse product over leaves in blocks of rows.
A control (``precision`` below float64) rounds q and w to that type,
multiplies and sums in float64, rounds the row to that type, and puts that
row's own top k in the program's place.
"""
import numpy as np
import scipy.sparse as sp

from bench import reference

_ROWS = 64          # reference rows built at once: 64 x N_ref float64


class Op:
    name = "topk"
    numbers = ("topk_gap",)

    def __init__(self, s, traffic: dict):
        self.engine = s.kernel.engine
        self.k = traffic["k"]
        self.T = s.cfg["forest"]["n_trees"]
        self.n_ref = len(s.X_train)

    def __call__(self, X: np.ndarray):
        return self.engine.topk(self.k, X=X)

    def sample(self, answers: list, idx: np.ndarray):
        return (np.concatenate([a[0] for a in answers])[idx],
                np.concatenate([a[1] for a in answers])[idx])

    def gaps(self, ref, X: np.ndarray, got,
             precision: str = "float64") -> dict:
        ix, v = (np.asarray(a) for a in got)
        n, k = len(X), self.k
        if ix.shape != (n, k) or v.shape != (n, k) or \
                not np.isfinite(v).all() or (ix < 0).any() or \
                (ix >= self.n_ref).any() or \
                (np.diff(np.sort(ix, axis=1), axis=1) == 0).any():
            return {"topk_gap": float("inf")}
        worst = 0.0
        blocks = proximity_rows(ref, X)
        if precision != "float64":       # a control in the program's place
            blocks = zip(blocks, proximity_rows(ref, X, precision))
        for b in blocks:
            (i0, P), Pl = (b, None) if precision == "float64" else \
                (b[0], b[1][1])
            bix, bv = ix[i0:i0 + len(P)], v[i0:i0 + len(P)]
            if Pl is not None:
                bix = np.argsort(-Pl, axis=1, kind="stable")[:, :k]
                bv = np.take_along_axis(Pl, bix, axis=1)
            want = -np.sort(np.partition(-P, k - 1, axis=1)[:, :k], axis=1)
            at = np.take_along_axis(P, bix, axis=1)
            gap = np.maximum(np.abs(bv - want), np.abs(at - bv))
            scale = np.maximum(P.max(axis=1), np.finfo(np.float64).tiny)
            worst = max(worst, float((gap.max(axis=1) / scale).max()))
        return {"topk_gap": worst}

    def work_bytes(self, n_rows: int) -> int:
        """The least any implementation that scores every reference row must
        read: each reference (row, tree) leaf id and weight once (4 + 4
        bytes), the batch's ids and weights, and the (n, k) answer's values
        and indices."""
        return 8 * self.n_ref * self.T + 8 * n_rows * self.T + \
            8 * n_rows * self.k


def proximity_rows(ref, X: np.ndarray, precision: str = "float64",
                   block: int = _ROWS):
    """(first row, dense (rows, N_ref) proximities) of ``X`` in blocks, from
    the reference's routing and weights, as a sparse product over leaves:
    Q (rows × leaves, q_t(x) at x's leaf of each tree) times W (leaves ×
    N_ref, w_t(j) at j's leaf), in float64."""
    store = reference._precision(precision)
    rnd = lambda a: np.asarray(a).astype(store).astype(np.float64)
    m = ref.model
    off = np.concatenate([[0], np.cumsum(m.n_nodes)])
    n_ref = len(m.train_leaf[0])
    W = sp.csr_matrix(
        (np.concatenate([rnd(w) for w in ref.w]),
         (np.concatenate([off[t] + m.train_leaf[t] for t in range(ref.T)]),
          np.tile(np.arange(n_ref), ref.T))), shape=(off[-1], n_ref))
    qn = ref.query_nodes(X)
    q = [rnd(ref.weights.query_weights(m, t, qn[t])) for t in range(ref.T)]
    for i0 in range(0, len(X), block):
        i1 = min(i0 + block, len(X))
        Q = sp.csr_matrix(
            (np.concatenate([w[i0:i1] for w in q]),
             (np.tile(np.arange(i1 - i0), ref.T),
              np.concatenate([off[t] + qn[t][i0:i1] for t in range(ref.T)]))),
            shape=(i1 - i0, off[-1]))
        yield i0, rnd((Q @ W).toarray())

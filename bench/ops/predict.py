"""``engine.predict(y, n_classes=C, X=batch)``: the class scores of a
batch of held-out rows.  Compared: ``predict_gap``, the largest class-score
gap to the reference over the row's total score."""
import numpy as np

from bench import work


class Op:
    name = "predict"
    numbers = ("predict_gap",)

    def __init__(self, s, traffic: dict):
        self.engine = s.kernel.engine
        self.y = s.kernel.ctx.y
        self.C = s.cfg["n_classes"]
        self.T = s.cfg["forest"]["n_trees"]

    def __call__(self, X: np.ndarray):
        return self.engine.predict(self.y, n_classes=self.C, X=X)

    def sample(self, answers: list, idx: np.ndarray):
        return np.concatenate(answers)[idx]

    def gaps(self, ref, X: np.ndarray, got, precision: str = "float64") -> dict:
        want = ref.predict(X, self.C)
        if precision != "float64":           # a control in the program's place
            got = ref.predict(X, self.C, precision)
        if got.shape != want.shape or not np.isfinite(got).all():
            return {"predict_gap": float("inf")}
        err = np.abs(got - want).max(axis=1)
        scale = np.maximum(want.sum(axis=1), np.finfo(np.float64).tiny)
        return {"predict_gap": float((err / scale).max())}

    def work_bytes(self, n_rows: int) -> int:
        return work.predict_bytes(n_rows, self.T, self.C)

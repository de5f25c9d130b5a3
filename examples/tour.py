"""A five-minute tour of spark_sklearn_tpu — every reference feature.

Mirrors the reference's README walkthrough (grid search, converter,
keyed models, gapply, sparse vectors) end to end on whatever devices
jax can see.  Run from the repo root:

    python examples/tour.py [--cpu]

--cpu forces the CPU backend (a chip belongs to one process at a time,
so use it when another process holds the accelerator).
"""

import sys

if "--cpu" in sys.argv:
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pandas as pd
from sklearn.datasets import load_digits
from sklearn.linear_model import LinearRegression, LogisticRegression
from sklearn.svm import SVC

import spark_sklearn_tpu as sst


def main():
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32)

    # 1. Distributed hyperparameter search (the flagship; reference:
    #    grid_search.py).  Drop-in for sklearn's GridSearchCV — and the
    #    legacy GridSearchCV(sc, est, grid) convention still works.
    gs = sst.GridSearchCV(
        LogisticRegression(max_iter=100),
        {"C": [0.01, 0.1, 1.0, 10.0]}, cv=3)
    gs.fit(X, y)
    print(f"[search]    best C={gs.best_params_['C']} "
          f"score={gs.best_score_:.4f} "
          f"backend={gs.search_report['backend']}")

    # 2. RandomizedSearchCV with sklearn's exact sampling semantics.
    from scipy.stats import loguniform
    rs = sst.RandomizedSearchCV(
        SVC(), {"C": loguniform(0.1, 100)}, n_iter=4, cv=3,
        random_state=0, refit=False)
    rs.fit(X[:400], y[:400])
    print(f"[randomized] best C={rs.best_params_['C']:.3f} "
          f"score={rs.best_score_:.4f}")

    # 3. Converter: fitted sklearn model -> device pytree and back
    #    (reference: converter.py, extended to 12+ families).
    conv = sst.Converter()
    tm = conv.toTPU(gs.best_estimator_)
    agree = float(np.mean(tm.predict(X[:200]) ==
                          gs.best_estimator_.predict(X[:200])))
    back = conv.toSKLearn(tm)
    print(f"[converter] device-predict agreement={agree:.3f} "
          f"round-trip type={type(back).__name__}")

    # 4. Keyed per-group model fleets (reference: keyed_models.py).
    rng = np.random.default_rng(0)
    df = pd.DataFrame({
        "k": np.repeat(list("abc"), 40),
        "x": [rng.normal(size=4) for _ in range(120)],
    })
    slopes = {"a": 1.0, "b": -2.0, "c": 0.5}
    df["y"] = [slopes[k] * v.sum() + 0.01 * rng.normal()
               for k, v in zip(df.k, df.x)]
    km = sst.KeyedEstimator(
        sklearnEstimator=LinearRegression(), keyCols=["k"],
        xCol="x", yCol="y").fit(df)
    out = km.transform(df)
    print(f"[keyed]     {len(km.keyedModels)} models "
          f"backend={km.backend} "
          f"pred[0]={out['output'].iloc[0]:.3f}")

    # 5. gapply: declared-schema grouped apply (reference:
    #    group_apply.py).
    def spread(key, pdf):
        return pd.DataFrame({"spread": [pdf["y"].max() - pdf["y"].min()]})

    g = sst.gapply(df.groupby("k"), spread,
                   schema={"spread": np.float64})
    print(f"[gapply]    per-key spreads={np.round(g['spread'].values, 2)}")

    # 6. Sparse rows end to end (reference: udt.py CSRVectorUDT).
    import scipy.sparse as sp
    m = sp.random(6, 8, density=0.4, format="csr", random_state=0)
    csr = sst.CSRMatrix.from_scipy(m)
    assert (csr.to_scipy() != m).nnz == 0
    print(f"[sparse]    CSRMatrix round trip ok "
          f"({csr.to_scipy().nnz} nonzeros)")


if __name__ == "__main__":
    main()

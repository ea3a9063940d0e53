"""The work one fit of a linear-family configuration NEEDS, from shapes alone.

Per program group: floating-point operations and the bytes that have to
cross HBM, whatever implements them.  Operations follow ``bench.py``'s
``bench_irls_mfu`` model: one IRLS lane-step is the Hessian ``2 n d^2``
plus ``6 n (d + 1)`` for margin, gradient and weights; one FISTA or
squared-hinge lane-step is a margin and a gradient, ``4 n (d + 1)``.  Bytes:
every step has to read the float32 block once — lanes (grid points x folds)
can share that read, and margin and gradient can share it too — so
``steps x n x d x 4``; per-row vectors are left out (under 1% at d = 128).
The metric sort and the refit are not counted as needed work.
"""

from __future__ import annotations

from typing import Any, Dict


def work(config: Dict[str, Any], traffic: Dict[str, Any], width: int
         ) -> Dict[str, Dict[str, float]]:
    n, d = float(traffic["rows"]), float(width)
    folds = int(config["cv"]["folds"])
    out: Dict[str, Dict[str, float]] = {}
    for fam in config["families"]:
        steps = int(fam["params"]["max_iter"])
        flops = bytes_ = 0.0
        if fam["key"] == "lr":
            newton = sum(1 for g in fam["grid"]
                         if g["reg_param"] * g.get("elastic_net", 0.0) <= 0.0)
            prox = len(fam["grid"]) - newton
            if newton:
                flops += newton * folds * steps * (
                    2.0 * n * d * d + 6.0 * n * (d + 1.0))
                bytes_ += steps * n * d * 4.0
            if prox:
                prox_steps = max(10 * steps, 300) + 30    # + power iteration
                flops += prox * folds * prox_steps * 4.0 * n * (d + 1.0)
                bytes_ += prox_steps * n * d * 4.0
        else:
            flops += len(fam["grid"]) * folds * steps * 4.0 * n * (d + 1.0)
            bytes_ += steps * n * d * 4.0
        out[fam["key"]] = {"flops": flops, "bytes": bytes_}
    return out

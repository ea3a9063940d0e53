"""Two-process jax.distributed worker (launched by test_distributed.py).

Each process: bootstrap the group via the framework's ``initialize``, build
``global_mesh``, ingest ONLY its ``host_local_rows`` slice, assemble the
global row-sharded array, and run a jitted column-stats program whose row
reductions become psums across processes — the driver/executor split the
reference exercises with Spark local[2] (TestSparkContext.scala:47-61).

argv: <process_id> <coordinator_port> <out_json_path>
"""
import json
import os
import sys

pid = int(sys.argv[1])
port = sys.argv[2]
out_path = sys.argv[3]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# belt and braces with the env var above (as in tests/conftest.py): two
# workers must never race for a chip
jax.config.update("jax_platforms", "cpu")

from transmogrifai_tpu.parallel import distributed  # noqa: E402

distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                       num_processes=2, process_id=pid)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()

mesh = distributed.global_mesh()  # (data=4, model=1) over both processes
n, d = 1024, 8
rng = np.random.default_rng(0)
x_full = rng.normal(size=(n, d)).astype(np.float32)
y_full = (rng.random(n) < 0.5).astype(np.float32)

# each process materializes ONLY its host-local slice (the readers' contract)
sl = distributed.host_local_rows(n)
x_local, y_local = x_full[sl], y_full[sl]

sx = NamedSharding(mesh, P("data", None))
sy = NamedSharding(mesh, P("data"))
x = jax.make_array_from_process_local_data(sx, x_local)
y = jax.make_array_from_process_local_data(sy, y_local)


@jax.jit
def col_stats(x, y):
    mean = x.mean(axis=0)
    var = x.var(axis=0)
    xc = x - mean
    yc = y - y.mean()
    cov = (xc * yc[:, None]).mean(axis=0)
    corr = cov / jnp.maximum(xc.std(axis=0) * yc.std(), 1e-12)
    return mean, var, corr


mean, var, corr = [np.asarray(v) for v in col_stats(x, y)]

# --- GBT across processes (VERDICT r4 #7): the tree-histogram psum is the
# Rabit-equivalent — fit a small GBT on the global mesh, rows sharded over
# both processes; the per-level histogram contractions reduce over the data
# axis via GSPMD-inserted psums.  Trees come out replicated (every process
# holds the full model); the test matches them against a single-process fit
# on the same rows.
from transmogrifai_tpu.models.trees import _fit_gbt  # noqa: E402

n_bins = 8
binned_full = rng.integers(0, n_bins + 1, size=(n, d)).astype(np.int32)
w_full = np.ones(n, np.float32)
sb = NamedSharding(mesh, P("data", None))
binned = jax.make_array_from_process_local_data(sb, binned_full[sl])
w = jax.make_array_from_process_local_data(sy, w_full[sl])

with mesh:
    margin, trees = _fit_gbt(
        binned, y, w, jax.random.PRNGKey(7), n_rounds=2, max_depth=2,
        n_bins=n_bins, objective="binary:logistic", num_class=1,
        subsample=1.0, colsample_bytree=1.0, colsample_bylevel=1.0,
        eta=jnp.float32(0.3), reg_lambda=jnp.float32(1.0),
        alpha=jnp.float32(0.0), gamma=jnp.float32(0.0),
        min_child_weight=jnp.float32(1.0), scale_pos_weight=jnp.float32(1.0),
        max_delta_step=jnp.float32(0.0),
        base_score=jnp.zeros(1, jnp.float32))
    # row-sharded margins reduce to a replicated scalar for the parity check
    margin_sum = float(jax.jit(lambda m: m.sum())(margin))

tree_arrays = {k: np.asarray(v).tolist()
               for k, v in trees._asdict().items()}

info = distributed.process_info()
if pid == 0:
    with open(out_path, "w") as fh:
        json.dump({"mean": mean.tolist(), "var": var.tolist(),
                   "corr": corr.tolist(), "info": info,
                   "gbt_trees": tree_arrays,
                   "gbt_margin_sum": margin_sum}, fh)
print("WORKER_OK", pid, flush=True)

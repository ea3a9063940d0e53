"""Pallas fused kernels for the tree hot loops + the kernel dispatch layer.

Reference capability (SURVEY §2.9): the reference's GBT/RF speed comes from
XGBoost4J's native C++ histogram kernels over the JNI; this package is the
TPU-native equivalent — hand-scheduled Pallas kernels for the memory-layout-
bound pieces of tree growth (histogram build, split scan) and the serving
encode prefix (one-hot / bucketize), behind a dispatch layer that keeps the
tuned XLA formulation as the always-available reference path.

Modules:

- :mod:`.dispatch` — mode resolution (``TMOG_PALLAS``: compiled Pallas on
  TPU, ``pallas.interpret=True`` for CPU/CI parity tests, XLA reference as
  escape hatch), VMEM admission guards, the cache token that keys every
  ``run_cached`` executable and plan fingerprint on the kernel choice, and
  the env-overridable tuning knobs (``TMOG_HIST_CHUNK``, ...).
- :mod:`.histogram` — fused histogram-build kernel: row chunks stream
  through VMEM, per-(node, class, feature, bin) grad/hess histograms
  accumulate in a VMEM-resident accumulator (exact-int8 path included),
  plus the standalone XLA reference formulation.
- :mod:`.splitscan` — fused split-scan kernel (bin cumulative sums + gain +
  argmax over the features x bins axis) and its XLA reference — the exact
  split-search math ``models/trees.py`` runs, factored to one place so both
  paths share one definition.
- :mod:`.encode` — fused serving-prefix encode kernels: level-code one-hot
  (``ops/onehot.py``) and right-inclusive bucketize one-hot
  (``ops/bucketizers.py``).
- :mod:`.routing` — the tree walk's row select: the level's columns
  gathered on the MXU, the compare-reduce, and the routing kernel.
- :mod:`.descent` — one step of the linear SVC's squared-hinge descent
  (margins, residuals and gradient) in one read of the block, and the
  XLA formulation it replaces.

Tuning: every kernel resolves its schedule parameters as explicit arg >
env knob > the persistent autotuner's verified winner for the shape class
(:mod:`transmogrifai_tpu.perf.autotune`) > module default.  Adopted winners
ride ``cache_token()`` so tuned and untuned processes never alias
executables or deploy artifacts.

Parity discipline (docs/performance.md "Pallas fused tree kernels"):
interpret-mode kernels are pinned bitwise-equal to the exact-int8 GEMM
reference in tier-1 (tests/test_kernels.py); compiled-TPU variants are
``slow``/TPU-gated.  The IR golden corpus registers the kernel program
families (checkers/irsnap.py) so ``tools/ir_gate.py`` pins them.
"""

from .dispatch import (  # noqa: F401
    cache_token,
    force_kernel_mode,
    kernel_mode,
    kernel_provenance,
    tuning_int,
)

"""ArtifactStore — content-addressed on-disk store of serving executables.

Pack side (build/CI host): compile + warm a fitted model's serving plan,
serialize every bucket executable (``perf.programs.serialize_compiled``)
into ``objects/<dd>/<digest>.aotx`` where the digest is the *executable
cache key* — plan fingerprint × bucket × ``mesh_token()`` ×
kernel-dispatch ``cache_token()`` — and write the DeployBundle manifest
(bundle.py) beside the model checkpoint.

Hydrate side (replica boot): verify the manifest (integrity hashes first —
no payload byte reaches pickle before its sha256 matches), then adopt each
deserialized executable into the live plan under the exact key a live
compile would have used (``CompiledScoringPlan.adopt_executable``), so the
process-wide executable cache dedups later tenants and ``warm()`` finds
the full ladder resident: ``boot_backend_compiles == 0``.

Every decision is observable: ``artifact_hydrated`` / ``artifact_miss`` /
``artifact_refused`` flight events (obs/flight.py), process-wide hit/miss/
refusal counters (``artifact_store_stats`` — ``cli deploy boot`` reports
them in its summary), and TM510 diagnostics
for every refusal.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..obs import flight as obs_flight
from .bundle import (
    BUNDLE_VERSION,
    MANIFEST_NAME,
    MODEL_DIR,
    OBJECTS_DIR,
    DeployBundle,
    check_bundle,
    environment_provenance,
    ir_corpus_fingerprints,
)

log = logging.getLogger(__name__)

#: process-wide warm-start accounting: where did executables come from?
#: Reported by ``cli deploy boot`` (``artifact_store``).
_STATS: Dict[str, int] = {"hits": 0, "misses": 0, "refusals": 0, "packed": 0}
_STATS_LOCK = threading.Lock()


def artifact_store_stats() -> Dict[str, int]:
    """Process-wide artifact counters: ``hits`` (buckets hydrated from an
    artifact), ``misses`` (buckets that fell back to live compilation),
    ``refusals`` (whole artifacts refused with TM510), ``packed``."""
    with _STATS_LOCK:
        return dict(_STATS)


def reset_artifact_store_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def artifact_key(fingerprint: str, bucket: int, *,
                 mesh_token_str: Optional[str] = None,
                 kernel_token: Optional[str] = None) -> str:
    """Content address of one executable object: the same anatomy as the
    in-process executable cache key — plan fingerprint × bucket ×
    mesh token × kernel-dispatch token.  The fingerprint already folds the
    ambient mesh and kernel mode in (workflow/plan.py), but the key spells
    them out so the on-disk address is self-describing and never relies on
    the fingerprint's internals."""
    if mesh_token_str is None or kernel_token is None:
        env = environment_provenance()
        mesh_token_str = env["meshToken"] if mesh_token_str is None \
            else mesh_token_str
        kernel_token = env["kernelToken"] if kernel_token is None \
            else kernel_token
    h = hashlib.blake2b(digest_size=20)
    h.update(json.dumps(["tmog-aot", BUNDLE_VERSION, fingerprint,
                         int(bucket), mesh_token_str, kernel_token]).encode())
    return h.hexdigest()


def _write_atomic(path: str, data: bytes) -> None:
    """tmp + rename so a crashed pack never leaves a half-written object a
    later verify could read as truncation of a *finished* pack."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as fh:
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


class ArtifactStore:
    """One artifact dir (= one DeployBundle): pack, verify, hydrate."""

    def __init__(self, root: str):
        self.root = str(root)
        # serializes this process' writers; cross-process safety comes from
        # the tmp+rename discipline, not from this lock
        self._write_lock = threading.Lock()

    # -- pack ----------------------------------------------------------------
    def pack(self, model, *, min_bucket: int = 8, max_bucket: int = 1024,
             buckets: Optional[Sequence[int]] = None,
             goldens_dir: Optional[str] = None,
             precision: Optional[str] = None) -> DeployBundle:
        """Compile + warm ``model``'s serving plan and pack it: model
        checkpoint, per-bucket serialized executables, manifest.

        ``precision`` packs the plan at a reduced numeric class
        (serve/plan.py Precision); the class joins the plan fingerprint,
        hence every ``artifact_key``, so a bf16/int8 artifact can never
        hydrate an f32 tenant (or vice versa), and it is recorded in the
        manifest so ``verify(model)`` recomputes the live content
        fingerprint at the SAME class.

        Raises ``ValueError`` for a host-only model (no device prefix means
        no executables — an empty artifact would be refused by every
        verifier, so refusing to *create* one keeps the contract symmetric)
        and ``TypeError`` when the jax build cannot serialize executables.
        """
        from ..perf.programs import serialize_compiled
        from ..serve.plan import CompiledScoringPlan

        plan = CompiledScoringPlan(model, min_bucket=min_bucket,
                                   max_bucket=max_bucket,
                                   precision=precision)
        if not plan.device_stage_uids:
            raise ValueError(
                "model has no device prefix — there are no executables to "
                "pack; host-only models cold-start without XLA anyway")
        ladder = list(buckets) if buckets is not None \
            else plan.bucket_ladder()

        env = environment_provenance()
        objects: Dict[str, Dict[str, Any]] = {}
        with self._write_lock:
            model.save(os.path.join(self.root, MODEL_DIR))
            for b in ladder:
                blob = serialize_compiled(plan.executable(b))
                digest = artifact_key(plan.fingerprint, b,
                                      mesh_token_str=env["meshToken"],
                                      kernel_token=env["kernelToken"])
                rel = os.path.join(OBJECTS_DIR, digest[:2],
                                   f"{digest}.aotx")
                _write_atomic(os.path.join(self.root, rel), blob)
                objects[str(int(b))] = {
                    "file": rel,
                    "keyDigest": digest,
                    "sha256": hashlib.sha256(blob).hexdigest(),
                    "size": len(blob),
                }
            manifest = {
                "bundleVersion": BUNDLE_VERSION,
                "createdAt": round(time.time(), 3),
                "model": {
                    "path": MODEL_DIR,
                    "resultFeatures": [f.name for f in
                                       model.result_features],
                },
                "plan": {
                    "fingerprint": plan.fingerprint,
                    "contentFingerprint": plan.content_fingerprint,
                    "precision": plan.precision,
                    "minBucket": plan.min_bucket,
                    "maxBucket": plan.max_bucket,
                    "buckets": [int(b) for b in ladder],
                    "entrySpecs": [[list(t), d]
                                   for t, d in plan.entry_specs],
                    "objects": objects,
                },
                "environment": env,
                "irCorpus": ir_corpus_fingerprints(goldens_dir),
            }
            _write_atomic(os.path.join(self.root, MANIFEST_NAME),
                          (json.dumps(manifest, indent=2, sort_keys=True)
                           + "\n").encode())
        _bump("packed")
        obs_flight.record_event("artifact_packed", root=self.root,
                                fingerprint=plan.fingerprint,
                                buckets=[int(b) for b in ladder])
        return DeployBundle(root=self.root, manifest=manifest)

    # -- verify ---------------------------------------------------------------
    def verify(self, model=None, *, min_bucket: Optional[int] = None,
               max_bucket: Optional[int] = None,
               live_corpus: Optional[Dict[str, Any]] = None
               ) -> Tuple[Any, List[str]]:
        """(TM510 DiagnosticReport, drift warnings) for this artifact dir.

        With ``model``, the live plan's content fingerprint is recomputed
        and compared (staleness); without it only structure, integrity, and
        provenance are checked.  ``live_corpus`` (see
        ``bundle.ir_corpus_fingerprints``) arms the IR-corpus drift check —
        the deploy gate's contract.
        """
        from ..checkers.diagnostics import DiagnosticReport

        try:
            bundle = DeployBundle.load(self.root)
        except (OSError, ValueError) as e:
            from ..checkers.diagnostics import make_diagnostic

            report = DiagnosticReport()
            report.diagnostics.append(make_diagnostic(
                "TM510", f"artifact manifest unreadable: {e}",
                location=os.path.join(self.root, MANIFEST_NAME)))
            return report, []
        content_fp = None
        if model is not None:
            from ..serve.plan import CompiledScoringPlan

            mb = bundle.plan.get("minBucket", 8) if min_bucket is None \
                else min_bucket
            xb = bundle.plan.get("maxBucket", 1024) if max_bucket is None \
                else max_bucket
            content_fp = CompiledScoringPlan(
                model, min_bucket=mb, max_bucket=xb,
                precision=bundle.plan.get("precision")).content_fingerprint
        return check_bundle(bundle, content_fingerprint=content_fp,
                            live_corpus=live_corpus)

    # -- hydrate ---------------------------------------------------------------
    def hydrate(self, plan, tenant: Optional[str] = None) -> Dict[str, Any]:
        """Adopt this artifact's executables into ``plan``; never raises.

        Fail-closed: integrity, version, and content-fingerprint problems
        refuse the WHOLE artifact (TM510 + ``artifact_refused`` flight
        event) before a single payload byte is unpickled, and a refusal
        adopts nothing — the caller's ``warm()`` then live-compiles as if
        no artifact existed.  Environment drift (mesh/device/kernel) is a
        clean miss: a warning + ``artifact_miss`` event, live compilation.

        Returns ``{"hydrated": [buckets], "refused": bool,
        "reasons": [...], "drift": [...]}``.
        """
        out: Dict[str, Any] = {"hydrated": [], "refused": False,
                               "reasons": [], "drift": []}

        def refused(reasons: List[str]) -> Dict[str, Any]:
            out["refused"] = True
            out["reasons"] = reasons
            _bump("refusals")
            _bump("misses", len(plan.bucket_ladder()))
            for r in reasons:
                log.warning("TM510 deploy artifact refused (%s): %s",
                            self.root, r)
            obs_flight.record_event("artifact_refused", code="TM510",
                                    root=self.root, tenant=tenant,
                                    reasons=reasons[:8])
            return out

        try:
            bundle = DeployBundle.load(self.root)
        except (OSError, ValueError) as e:
            return refused([f"artifact manifest unreadable: {e}"])

        report, drift = check_bundle(
            bundle, content_fingerprint=plan.content_fingerprint)
        out["drift"] = drift
        if report.errors():
            return refused([d.message for d in report.errors()])

        manifest_plan = bundle.plan
        if manifest_plan.get("fingerprint") != plan.fingerprint:
            # content verified equal above, so this is pure environment
            # drift: the executable key legitimately differs — miss cleanly
            reasons = drift or ["environment-qualified fingerprint differs "
                                "(packed under another mesh/kernel "
                                "environment)"]
            for r in reasons:
                log.warning("deploy artifact miss (%s): %s", self.root, r)
            _bump("misses", len(plan.bucket_ladder()))
            obs_flight.record_event("artifact_miss", root=self.root,
                                    tenant=tenant, reasons=reasons[:8])
            return out

        # integrity proven for every object (check_bundle hashed them all):
        # deserialize everything BEFORE adopting anything, so a payload the
        # current runtime cannot load refuses the artifact instead of
        # leaving the plan half-hydrated
        from ..perf.programs import deserialize_compiled

        wanted = set(plan.bucket_ladder())
        loaded: Dict[int, Any] = {}
        try:
            for bucket_s, meta in sorted(manifest_plan["objects"].items(),
                                         key=lambda kv: int(kv[0])):
                bucket = int(bucket_s)
                if bucket not in wanted:
                    continue
                with open(bundle.object_path(meta["file"]), "rb") as fh:
                    loaded[bucket] = deserialize_compiled(fh.read())
        except (OSError, ValueError, KeyError) as e:
            return refused([f"executable payload failed to load: {e}"])

        for bucket, compiled in sorted(loaded.items()):
            plan.adopt_executable(bucket, compiled)
        out["hydrated"] = sorted(loaded)
        _bump("hits", len(loaded))
        misses = sorted(wanted - set(loaded))
        if misses:
            _bump("misses", len(misses))
        obs_flight.record_event("artifact_hydrated", root=self.root,
                                tenant=tenant,
                                fingerprint=plan.fingerprint,
                                buckets=sorted(loaded),
                                live_compile_buckets=misses)
        return out

    def load_model(self):
        return DeployBundle.load(self.root).load_model()


def pack_model(model, root: str, **kwargs) -> DeployBundle:
    """Convenience wrapper: ``ArtifactStore(root).pack(model, **kwargs)``."""
    return ArtifactStore(root).pack(model, **kwargs)

"""Warm-start persistence: a restarted engine explains with zero compiles.

``ExplainEngine`` reaches steady state by AOT-compiling one executable per
(bucket, method-class, schedule, m, ...) key — seconds each — and one bucket
prep program per argument shape. On restart that whole set is gone. This
module persists it (ISSUE 10), alongside the autotune entries and the
adaptive hop-zero δ-history, with the checkpoint manager's atomicity
discipline (``checkpoint.manager.atomic_dir``: tmp-dir write, per-file
sha256 manifest, one ``os.replace``).

Two serialized forms of the executable set, tried in order at restore:

  * **native** (``jax.experimental.serialize_executable``): the compiled
    XLA executable itself — a true zero-compile restore (measured ~200×
    faster cold-start-to-first-explanation on the reduced LM). Pickle-level
    and device-level fragile, so it is only trusted when the manifest's
    recorded jax version AND device kind match the current process exactly;
  * **portable** (``jax.export`` StableHLO): versioned and
    device-independent, but XLA re-compiles the deserialized module at load
    (~1.4× — it saves tracing/lowering only). The fallback when the native
    payload is stale, refuses to load, or is absent: the native form is
    saved only when every executable serializes (XLA:CPU refuses some, so
    CPU restores go this way).

Any mismatch — corrupted file (sha256), different model fingerprint or
engine knobs (``ExplainEngine.warm_context``), unreadable pickle — warns
and falls back COLD: a warm state can make a restart slow again, never
wrong. Mesh-sharded executables are skipped at save (their shardings bind
process topology); mesh engines re-compile as before.

    eng = ExplainEngine(cfg, params, ...)
    eng.explain(traffic)                     # warm the executable set
    save_warm_state(eng, "results/warm")     # atomic, content-hashed
    ...process restarts...
    eng2 = ExplainEngine(cfg, params, ...)   # same model + knobs
    report = load_warm_state(eng2, "results/warm")
    eng2.explain(traffic)                    # zero compiles (report.via)
"""
from __future__ import annotations

import json
import os
import pickle
import warnings
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np
from jax import export as jexport
from jax.experimental import serialize_executable as _se

from repro.checkpoint.manager import atomic_dir, sha256_file
from repro.core import ig, perturb
from repro.core.schedule import Schedule
from repro.serve.autotune import device_kind

_MANIFEST = "manifest.json"
_NATIVE = "executables.pkl"
_PORTABLE = "exports.pkl"
_STATE = "state.json"
_FORMAT = 1


def _register_trees() -> None:
    """jax.export refuses unregistered NamedTuples in arg/result trees; the
    engine's programs carry these four. Registration is process-global and
    idempotent only by name — tolerate re-import."""
    for nt in (ig.IGResult, ig.IGState, Schedule, perturb.PerturbResult):
        try:
            jexport.register_namedtuple_serialization(
                nt, serialized_name=f"repro.{nt.__name__}"
            )
        except ValueError:
            pass  # already registered under this name


_register_trees()


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _pack_sds(sds: Any) -> tuple:
    """A pickle-stable form of a ShapeDtypeStruct tree: leaf (shape, dtype
    name) pairs + the treedef (PyTreeDefs pickle; ShapeDtypeStructs are not
    guaranteed to across jax versions)."""
    leaves, treedef = jax.tree.flatten(sds)
    return [(tuple(s.shape), s.dtype.name) for s in leaves], treedef


def _unpack_sds(packed: tuple) -> Any:
    specs, treedef = packed
    return jax.tree.unflatten(
        treedef, [jax.ShapeDtypeStruct(s, _np_dtype(d)) for s, d in specs]
    )


@dataclass
class WarmRestoreReport:
    """What ``load_warm_state`` did: ``restored`` with ``executables``
    entries via ``"native"`` or ``"export"``, or cold with a ``reason``."""

    restored: bool
    via: str = ""
    executables: int = 0
    reason: str = ""


def _cold(reason: str) -> WarmRestoreReport:
    warnings.warn(
        f"warm_state: {reason}; starting cold (correctness is unaffected)",
        stacklevel=3,
    )
    return WarmRestoreReport(restored=False, reason=reason)


def save_warm_state(engine: Any, directory: str) -> str:
    """Persist the engine's executable set + autotune entries + δ-history.

    Written with ``atomic_dir``: a crash mid-save leaves any previous warm
    state intact. Returns the directory path. Sharded executables and any
    entry ``jax.export`` cannot serialize are skipped with a warning — the
    restored engine simply compiles those keys again.
    """
    # the δ-history may imply elevated starting rungs the run itself never
    # compiled (history accumulates as it serves) — close the set first
    if getattr(engine, "hop_zero", False):
        engine.precompile_hop_zero_starts()
    # blobs stashed by a prior load_warm_state: a RESTORED executable has no
    # export info (its builder fn never ran this process) and a deserialized
    # executable cannot be re-serialized (the payload loses linked symbols),
    # so restore→save carries the original blobs forward instead of dropping
    # the entry — the cycle must never shrink the warm state
    carried = getattr(engine, "_warm_saved", {"native": {}, "portable": {}})
    # the bucket prep programs ride beside the executables, under their
    # ("prep", shapes) keys; no prep program is sharded
    entries = [
        *engine._cache.items(),
        *((key, (prep, None)) for key, prep in engine._prep_cache.items()),
    ]
    export_info = {**engine._export_info, **engine._prep_export_info}
    # None once any executable refuses native serialization: the set then
    # restores through the portable form alone
    native: Optional[list[dict]] = []
    portable: list[dict] = []
    skipped = 0
    for key, (compiled, shardings) in entries:
        if shardings is not None:
            skipped += 1
            continue
        info = export_info.get(key)
        if info is None:
            kept = False
            if key in carried["native"] and native is not None:
                native.append(carried["native"][key])
                kept = True
            if key in carried["portable"]:
                portable.append(carried["portable"][key])
                kept = True
            if not kept:
                skipped += 1
            continue
        fn, sds, donate = info
        if native is not None:
            try:
                payload, in_tree, out_tree = _se.serialize(compiled)
                native.append(
                    {"key": key, "payload": payload, "in_tree": in_tree,
                     "out_tree": out_tree}
                )
            except Exception as e:  # noqa: BLE001 — native form is best-effort too
                # e.g. XLA:CPU refuses some executables ("LessThan is not
                # serializable")
                warnings.warn(
                    f"warm_state: no native payload ({key[:2]}: {e}); the set "
                    "restores through the portable form",
                    stacklevel=2,
                )
                native = None
        try:
            exp = jexport.export(jax.jit(fn, donate_argnums=donate))(*sds)
            portable.append(
                {"key": key, "blob": exp.serialize(), "sds": _pack_sds(sds)}
            )
        except Exception as e:  # noqa: BLE001 — portable form is best-effort
            warnings.warn(
                f"warm_state: jax.export could not serialize {key[:2]}: {e}; "
                "the native payload still covers this entry",
                stacklevel=2,
            )
    if skipped:
        warnings.warn(
            f"warm_state: skipped {skipped} sharded/unexportable executables "
            "(mesh engines recompile on restart)",
            stacklevel=2,
        )
    state = {
        "autotune_device": (
            engine._autotune_cache.device if engine._autotune_cache else ""
        ),
        "autotune_entries": (
            engine._autotune_cache.entries if engine._autotune_cache else {}
        ),
        "delta_hist": {
            f"{s}:{meth}": list(map(int, hist))
            for (s, meth), hist in engine._delta_hist.items()
        },
    }
    native = native or []
    with atomic_dir(directory) as tmp:
        with open(os.path.join(tmp, _NATIVE), "wb") as fh:
            pickle.dump(native, fh)
        with open(os.path.join(tmp, _PORTABLE), "wb") as fh:
            pickle.dump(portable, fh)
        with open(os.path.join(tmp, _STATE), "w") as fh:
            json.dump(state, fh)
        manifest = {
            "format": _FORMAT,
            "jax_version": jax.__version__,
            "device_kind": device_kind(),
            "context": engine.warm_context(),
            "n_executables": len(
                {b["key"] for b in native} | {b["key"] for b in portable}
            ),
            "files": {
                name: sha256_file(os.path.join(tmp, name))
                for name in (_NATIVE, _PORTABLE, _STATE)
            },
        }
        with open(os.path.join(tmp, _MANIFEST), "w") as fh:
            json.dump(manifest, fh, indent=1)
    return directory


def load_warm_state(engine: Any, directory: str) -> WarmRestoreReport:
    """Validate + restore a persisted warm state into ``engine``.

    Restore order matters: autotune entries land first (executable keys
    carry the resolved per-bucket ``HotpathConfig``, so the engine must
    resolve the same configs the save-time engine did), then the δ-history,
    then the executables — native form when the manifest's jax version and
    device kind match this process and it holds the set, else the portable
    ``jax.export`` form.
    EVERY validation failure falls back cold with a warning; a partial
    native restore is rolled back before trying the portable form.
    """
    mpath = os.path.join(directory, _MANIFEST)
    if not os.path.isfile(mpath):
        return WarmRestoreReport(restored=False, reason="no warm state")
    try:
        with open(mpath) as fh:
            manifest = json.load(fh)
    except (json.JSONDecodeError, OSError) as e:
        return _cold(f"unreadable manifest ({e})")
    if manifest.get("format") != _FORMAT:
        return _cold(f"unknown format {manifest.get('format')!r}")
    for name, digest in manifest.get("files", {}).items():
        path = os.path.join(directory, name)
        if not os.path.isfile(path) or sha256_file(path) != digest:
            return _cold(f"corrupted or missing shard {name!r}")
    if manifest.get("context") != engine.warm_context():
        return _cold("engine context mismatch (different model or knobs)")

    try:
        with open(os.path.join(directory, _STATE)) as fh:
            state = json.load(fh)
    except (json.JSONDecodeError, OSError) as e:
        return _cold(f"unreadable state ({e})")
    if engine._autotune_cache is not None and state.get("autotune_entries"):
        engine._autotune_cache.entries = dict(state["autotune_entries"])
    hist = {}
    for skey, values in state.get("delta_hist", {}).items():
        s, meth = skey.split(":", 1)
        hist[(int(s), meth)] = [int(v) for v in values]
    engine._delta_hist.update(hist)

    native_ok = (
        manifest.get("jax_version") == jax.__version__
        and manifest.get("device_kind") == device_kind()
    )
    if native_ok:
        restored: dict = {}
        try:
            with open(os.path.join(directory, _NATIVE), "rb") as fh:
                blobs = pickle.load(fh)
            for b in blobs:
                restored[b["key"]] = (
                    _se.deserialize_and_load(
                        b["payload"], b["in_tree"], b["out_tree"]
                    ),
                    None,
                )
        except Exception as e:  # noqa: BLE001 — stale native payloads degrade
            restored = {}
            warnings.warn(
                f"warm_state: native restore failed ({e}); "
                "trying the portable jax.export form",
                stacklevel=2,
            )
        if restored:
            _install(engine, restored)
            _stash_blobs(engine, directory, with_native=True)
            return WarmRestoreReport(
                restored=True, via="native", executables=len(restored)
            )
    try:
        with open(os.path.join(directory, _PORTABLE), "rb") as fh:
            blobs = pickle.load(fh)
        restored = {}
        for b in blobs:
            exp = jexport.deserialize(b["blob"])
            sds = _unpack_sds(b["sds"])
            # donation is not re-requested here: the exported module is
            # re-compiled by XLA anyway and donation is a perf hint only
            restored[b["key"]] = (jax.jit(exp.call).lower(*sds).compile(), None)
        _install(engine, restored)
        _stash_blobs(engine, directory, with_native=False)
        return WarmRestoreReport(
            restored=True, via="export", executables=len(restored)
        )
    except Exception as e:  # noqa: BLE001 — never let a bad blob kill serving
        return _cold(f"portable restore failed ({e})")


def _install(engine: Any, restored: dict) -> None:
    """Put restored entries where the engine looks them up: prep programs
    (``("prep", shapes)`` keys) in ``_prep_cache``, executables in
    ``_cache``."""
    for key, (compiled, shardings) in restored.items():
        if key[0] == "prep":
            engine._prep_cache[key] = compiled
        else:
            engine._cache[key] = (compiled, shardings)


def _stash_blobs(engine: Any, directory: str, *, with_native: bool) -> None:
    """Keep the restored blobs on the engine so ``save_warm_state`` can carry
    them forward (restored executables cannot be re-serialized). The native
    payloads are carried only when they were trusted at load (version and
    device matched) — a new save's manifest records the CURRENT jax version,
    and it must never vouch for a stale payload."""
    stash = {"native": {}, "portable": {}}
    try:
        if with_native:
            with open(os.path.join(directory, _NATIVE), "rb") as fh:
                stash["native"] = {b["key"]: b for b in pickle.load(fh)}
        with open(os.path.join(directory, _PORTABLE), "rb") as fh:
            stash["portable"] = {b["key"]: b for b in pickle.load(fh)}
    except Exception:  # noqa: BLE001 — the stash is best-effort
        pass
    engine._warm_saved = stash

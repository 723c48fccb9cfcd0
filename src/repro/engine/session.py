"""Persistent inference sessions: compile once, predict anywhere.

``compile(model, input_spec, ...)`` owns the whole NeoCPU lifecycle the
paper argues belongs to one system (§3): it runs a pass ``Pipeline`` over
the graph, keeps the schedule database, auto-calibrates the host transform
bandwidth when tuning is measured, binds parameters once (including the
bind-time panel pre-layout for ``patch_gemm`` weights), and specializes the
executable per batch size on demand.

The session is also the persistence boundary: ``session.save(path)``
writes a versioned artifact — the planned graphs, schedules, layouts, the
schedule database, and the *pre-transformed* weights (via
``checkpoint.store.CheckpointStore``) — and ``InferenceSession.load(path)``
in a fresh process goes load -> predict with **zero schedule search** and
zero weight re-transformation (the main lever for the ROADMAP's
"fast cold start" item; ``core.local_search.search_calls()`` is the spy
that proves it).

    session = compile("resnet-18", (1, 3, 224, 224), tuning="cached")
    y = session.predict(x)
    session.save("artifact/")
    # ... fresh process ...
    y2 = InferenceSession.load("artifact/").predict(x)   # bit-identical

Artifact layout (version 4):

    <path>/manifest.json   format, version, input spec, tuning,
                           transform_bw, schedule-db blob, pipeline/report
                           metadata, the "specializations" table (batch ->
                           plan-file reference), a "checksums" table
                           (relative path -> SHA-256 of every other file
                           in the artifact), a "quantized" section (None,
                           or a reference to <path>/quantized.json), and
                           an optional "source" section (the *logical*
                           graph) that — together with <path>/source/ —
                           lets a loaded session legally specialize unseen
                           batch sizes
    <path>/plans/          batch_<b>.json: one specialization's plan
    <path>/weights/        CheckpointStore; step_<batch>/ holds the bound
                           (physical-layout) params of one specialization
                           — int8 weight codes for quantized convs, stored
                           and checksummed like any other array
    <path>/quantized.json  (dtype="int8" sessions only) the quantization
                           scheme plus the per-conv dtype map of every
                           specialization, checksummed like any other file
    <path>/source/         CheckpointStore (one step): the raw logical
                           params, present iff manifest["source"] is

Integrity: ``save`` builds the whole artifact in a sibling temp directory
and atomically swaps it in, so a crash mid-save never leaves a
half-written artifact where a loadable one stood.  ``load`` verifies
every checksummed file before deserializing anything and raises the typed
:class:`ArtifactCorruptError` (a bit-flipped weight blob or plan is
refused, never silently served); structurally-broken artifacts raise
:class:`ArtifactError`.  Both subclass ``ValueError``.

Older artifacts load through a **migration hook chain**: ``_MIGRATIONS``
maps each historical version to a function upgrading a manifest one
version forward, applied in sequence until the current version is reached
(v1 -> v2 renames "batches" to "specializations" and marks the source as
absent; v2 -> v3 marks the checksums as absent — migrated manifests keep
their inline plans and load unverified until re-saved; v3 -> v4 marks the
quantized payload as absent).  Artifacts whose checksums migrated to
``None`` load with one explicit :class:`UnverifiedArtifactWarning`, and a
plain load -> save round trip backfills the checksums (``save`` always
writes a fresh table), upgrading the artifact to verified integrity.  A
*future* version — or a manifest that is not valid JSON — is still
rejected cleanly.  ``register_migration`` lets later builds extend the
chain.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax.numpy as jnp

from repro.checkpoint.store import (CheckpointStore, dir_checksums,
                                    sha256_file)
from repro.core.graph import Graph
from repro.core.layout import Layout, LayoutKind
from repro.core.local_search import ScheduleDatabase
from repro.core.pipeline import MODES, Pipeline, Plan
from repro.core.schedule import ConvSchedule
from repro.core.transform_elim import PlannedGraph
from repro.engine.executor import CompiledModel, compile_model
from repro.engine.telemetry import SizeHistogram
from repro.nn.init import Params, init_params

ARTIFACT_FORMAT = "neocpu-inference-session"
ARTIFACT_VERSION = 5

SESSION_DTYPES = ("fp32", "int8")


class ArtifactError(ValueError):
    """A saved artifact cannot be loaded: missing, structurally invalid,
    or from an unsupported version.  Subclasses ``ValueError`` so
    pre-typed callers keep working."""


class UnverifiedArtifactWarning(UserWarning):
    """A pre-v3 artifact is loading without checksum verification (its
    manifest predates the integrity table).  Re-saving the loaded session
    backfills the checksums, so one load -> save round trip upgrades the
    artifact to verified integrity."""


class ArtifactCorruptError(ArtifactError):
    """The artifact's bytes do not match what was saved: a checksum
    mismatch, a truncated blob, or unparseable JSON.  Corrupt weights are
    *refused*, never silently served."""

# version -> hook upgrading a manifest from exactly that version to the
# next one; load() walks the chain until ARTIFACT_VERSION is reached
_MIGRATIONS: Dict[int, Callable[[Dict[str, Any], Path], Dict[str, Any]]] = {}


def register_migration(from_version: int) -> Callable:
    """Decorator: install a manifest migration hook for ``from_version``.
    The hook receives (manifest, artifact_path), mutates/returns the
    manifest in the *next* version's shape, and must bump "version"."""
    def deco(fn: Callable[[Dict[str, Any], Path], Dict[str, Any]]):
        _MIGRATIONS[from_version] = fn
        return fn
    return deco


@register_migration(1)
def _migrate_v1_to_v2(manifest: Dict[str, Any], path: Path) -> Dict[str, Any]:
    """v1 -> v2: per-batch plans moved from "batches" to "specializations";
    v1 never packed the logical graph + raw weights, so "source" is absent
    (the loaded session stays frozen, exactly as v1 sessions were)."""
    manifest["specializations"] = manifest.pop("batches")
    manifest["source"] = None
    manifest["version"] = 2
    return manifest


@register_migration(2)
def _migrate_v2_to_v3(manifest: Dict[str, Any], path: Path) -> Dict[str, Any]:
    """v2 -> v3: per-file SHA-256 checksums and per-batch plan files.
    Pre-v3 artifacts recorded neither, so "checksums" is marked absent
    (the artifact loads unverified — re-save to gain integrity checking)
    and the inline plan dicts stay where they are (the loader accepts
    both inline plans and v3 file references)."""
    manifest["checksums"] = None
    manifest["version"] = 3
    return manifest


@register_migration(3)
def _migrate_v3_to_v4(manifest: Dict[str, Any], path: Path) -> Dict[str, Any]:
    """v3 -> v4: the optional quantized payload (``quantized.json`` +
    manifest reference, written by ``dtype="int8"`` sessions).  Pre-v4
    artifacts are all fp32, so "quantized" is simply absent."""
    manifest["quantized"] = None
    manifest["version"] = 4
    return manifest


@register_migration(4)
def _migrate_v4_to_v5(manifest: Dict[str, Any], path: Path) -> Dict[str, Any]:
    """v4 -> v5: the optional ``lm`` manifest section (LM sessions: config
    + seq-bucket set + prompt-traffic provenance, loaded by
    ``LMSession.load``).  Pre-v5 artifacts are all CNN sessions, so "lm"
    is simply absent."""
    manifest["lm"] = None
    manifest["version"] = 5
    return manifest


# ---------------------------------------------------------------------------
# Plan / graph (de)serialization
# ---------------------------------------------------------------------------

def _enc_attr(v: Any) -> Any:
    if isinstance(v, Layout):
        return {"__layout__": v.kind.value, "block": v.block}
    if isinstance(v, tuple):
        return {"__tuple__": [_enc_attr(x) for x in v]}
    return v


def _dec_attr(v: Any) -> Any:
    if isinstance(v, dict) and "__layout__" in v:
        kind = LayoutKind(v["__layout__"])
        return Layout(kind, v["block"]) if kind is LayoutKind.NCHWc \
            else Layout(kind)
    if isinstance(v, dict) and "__tuple__" in v:
        return tuple(_dec_attr(x) for x in v["__tuple__"])
    return v


def _graph_to_json(g: Graph) -> Dict[str, Any]:
    return {"nodes": [{"name": n.name, "op": n.op, "inputs": list(n.inputs),
                       "attrs": {k: _enc_attr(v) for k, v in n.attrs.items()},
                       "shape": list(n.shape) if n.shape else None}
                      for n in g.topo_order()],
            "outputs": list(g.outputs)}


def _graph_from_json(js: Dict[str, Any]) -> Graph:
    g = Graph()
    for rec in js["nodes"]:           # serialized in topo order
        g.add(rec["name"], rec["op"], rec["inputs"],
              **{k: _dec_attr(v) for k, v in rec["attrs"].items()})
        if rec["shape"] is not None:
            g.nodes[rec["name"]].shape = tuple(rec["shape"])
    for o in js["outputs"]:
        g.mark_output(o)
    return g


def _plan_to_json(plan: Plan) -> Dict[str, Any]:
    p = plan.planned
    return {
        "mode": plan.mode,
        "graph": _graph_to_json(p.graph),
        "layouts": {name: _enc_attr(lay) for name, lay in p.layouts.items()},
        "schedules": {name: dataclasses.asdict(s)
                      for name, s in p.schedules.items()},
        "n_transforms": p.n_transforms,
        "transform_bytes_total": p.transform_bytes_total,
        "predicted": {"conv_s": plan.predicted_conv_s,
                      "transform_s": plan.predicted_transform_s,
                      "epilogue_s": plan.predicted_epilogue_s},
        "report": plan.report.to_json() if plan.report else None,
    }


def _plan_from_json(js: Dict[str, Any]) -> Plan:
    planned = PlannedGraph(
        graph=_graph_from_json(js["graph"]),
        layouts={name: _dec_attr(v) for name, v in js["layouts"].items()},
        schedules={name: ConvSchedule(**s)
                   for name, s in js["schedules"].items()},
        n_transforms=js["n_transforms"],
        transform_bytes_total=js["transform_bytes_total"])
    pred = js["predicted"]
    # solution/fusion/report are plan-time provenance, not needed to
    # execute; the report's JSON form is kept in the manifest only
    return Plan(planned=planned, mode=js["mode"], solution=None,
                predicted_conv_s=pred["conv_s"],
                predicted_transform_s=pred["transform_s"],
                predicted_epilogue_s=pred["epilogue_s"])


def _params_to_flat_ok(params: Params) -> Params:
    """Param leaf names ('w', 'b', 'scale', ...) never contain dots, so the
    CheckpointStore's dotted flat paths split back unambiguously."""
    for p in params.values():
        for leaf in p:
            assert "." not in leaf, f"param leaf {leaf!r} would not round-trip"
    return params


def _params_from_flat(leaves: Dict[str, Any]) -> Params:
    out: Params = {}
    for path, arr in leaves.items():
        node, leaf = path.rsplit(".", 1)
        out.setdefault(node, {})[leaf] = jnp.asarray(arr)
    return out


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------

class InferenceSession:
    """One compiled model: plans + bound weights, specialized per batch
    size.  Create with :func:`compile`; persist with :meth:`save` /
    :meth:`load`.  Sessions loaded from an artifact *without* a packed
    source are *frozen*: they execute their saved specializations but
    cannot re-plan new batch sizes.  Artifacts saved with
    ``include_source=True`` (the default when the session has its graph)
    also pack the logical graph + raw weights, so the loaded session can
    legally specialize unseen batch sizes — with zero schedule searches
    when the artifact's database already holds those workloads.

    ``specialize`` is thread-safe: concurrent requests for the same new
    batch size compile it exactly once (the serving driver's workers and
    user threads share one session)."""

    def __init__(self, *, graph: Optional[Graph],
                 base_shapes: Dict[str, Tuple[int, ...]],
                 params: Optional[Params],
                 pipeline: Optional[Pipeline],
                 db: Optional[ScheduleDatabase] = None,
                 tuning: str = "roofline",
                 transform_bw: Optional[float] = None,
                 search_budget: Tuple[int, int, int] = (6, 2, 3),
                 use_pallas: bool = False,
                 interpret: Optional[bool] = None,
                 dispatch: str = "whole", devices: int = 1,
                 dtype: str = "fp32",
                 model_name: Optional[str] = None) -> None:
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if dtype not in SESSION_DTYPES:
            raise ValueError(f"dtype {dtype!r} not in {SESSION_DTYPES}")
        self._graph = graph
        self._base_shapes = {k: tuple(v) for k, v in base_shapes.items()}
        self._params = params
        self.pipeline = pipeline
        self.db = db if db is not None else ScheduleDatabase()
        self.tuning = tuning
        self.transform_bw = transform_bw
        self.search_budget = search_budget
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.dispatch = dispatch
        self.devices = devices
        # "int8": specializations enumerate quantized schedules; the search
        # decides per conv, so the bound plan may be mixed-precision
        self.dtype = dtype
        self.model_name = model_name
        self._specialized: Dict[int, CompiledModel] = {}
        # measured request-size arrivals (recorded by the serving driver,
        # or fed manually); what save(buckets="auto") learns the next
        # artifact's bucket set from.  Bounded: O(max_bins) forever.
        self.traffic = SizeHistogram()
        # serializes planning/binding: two threads racing on the same new
        # batch size must not double-compile (and the schedule search /
        # executor must never run concurrently with itself)
        self._lock = threading.RLock()

    # -- introspection -------------------------------------------------------
    @property
    def input_spec(self) -> Dict[str, Tuple[int, ...]]:
        return dict(self._base_shapes)

    @property
    def batch_sizes(self):
        return sorted(self._specialized)

    @property
    def frozen(self) -> bool:
        """True for artifact-loaded sessions (no source graph to re-plan)."""
        return self._graph is None

    def plan_for(self, batch: int) -> Plan:
        return self.specialize(batch).plan

    # -- compilation ---------------------------------------------------------
    def _shapes_for(self, batch: int) -> Dict[str, Tuple[int, ...]]:
        return {k: (batch,) + v[1:] for k, v in self._base_shapes.items()}

    def _check_divisible(self, batch: int) -> None:
        if self.devices > 1 and batch % self.devices:
            raise ValueError(
                f"batch {batch} is not divisible by devices="
                f"{self.devices}: a bucket of size B on D devices means a "
                "per-device sub-batch of B/D, so every specialized bucket "
                "must divide evenly (pick a divisible bucket set, or "
                "compile with devices=1)")

    def specialize(self, batch: int) -> CompiledModel:
        """The executable for one batch size, planning+binding on first
        use (per-batch-size shape specialization).  With ``devices=D`` the
        plan is built at the per-device sub-batch ``batch // D`` — the
        shapes each device actually executes under the batch-sharded
        ``shard_map`` — so ``batch`` must divide by D.  Thread-safe:
        double-checked under the session lock, so concurrent callers of an
        unseen batch size plan+compile it exactly once."""
        m = self._specialized.get(batch)     # lock-free fast path
        if m is not None:
            return m
        self._check_divisible(batch)
        with self._lock:
            m = self._specialized.get(batch)
            if m is not None:                # another thread won the race
                return m
            if self.frozen:
                raise RuntimeError(
                    f"session loaded from an artifact has no batch-{batch} "
                    f"specialization (saved: {self.batch_sizes}) and no "
                    "source graph to re-plan; save the artifact with this "
                    "batch size or with include_source=True")
            plan = self.pipeline.run(
                self._graph, self._shapes_for(batch // self.devices),
                db=self.db,
                tuning=self.tuning, quantize=(self.dtype == "int8"),
                pallas=self.use_pallas, transform_bw=self.transform_bw,
                search_budget=self.search_budget)
            if (plan.report is not None
                    and plan.report.transform_bw is not None):
                # calibrated once (measured tuning); reused by later
                # specializations and cached in the saved artifact
                self.transform_bw = plan.report.transform_bw
            m = compile_model(plan, self._params,
                              use_pallas=self.use_pallas,
                              interpret=self.interpret,
                              dispatch=self.dispatch,
                              devices=self.devices)
            self._specialized[batch] = m
            return m

    # -- execution -----------------------------------------------------------
    def __call__(self, inputs: Dict[str, jnp.ndarray]):
        batch = int(next(iter(inputs.values())).shape[0])
        return self.specialize(batch)(inputs)

    def predict(self, x: jnp.ndarray):
        """Single-input convenience (the common CNN case); dispatches to
        the batch-size specialization of ``x``."""
        return self.specialize(int(x.shape[0])).predict(x)

    # -- memory accounting ---------------------------------------------------
    def memory_bytes(self) -> Dict[int, int]:
        """Bytes of bound parameters held per specialization — what a
        fleet memory budget accounts and what :meth:`release` frees."""
        with self._lock:
            return {batch: sum(int(arr.nbytes)
                               for node in m.params.values()
                               for arr in node.values())
                    for batch, m in self._specialized.items()}

    def release(self, batch: int) -> bool:
        """Drop the compiled specialization for ``batch``, freeing its
        bound params (LRU eviction under a fleet memory budget).  Returns
        True iff it existed.  A later ``specialize(batch)`` rebuilds it —
        with zero schedule searches when the database already holds the
        workloads — so eviction trades latency, never correctness.
        Frozen sessions refuse: they could never specialize it back."""
        with self._lock:
            if self.frozen:
                raise RuntimeError(
                    "cannot release a specialization of a frozen session "
                    "(no source graph to rebuild it from); its buckets "
                    "are pinned")
            return self._specialized.pop(batch, None) is not None

    # -- persistence ---------------------------------------------------------
    def save(self, path: Union[str, Path],
             include_source: Optional[bool] = None,
             buckets: Union[None, str, "Sequence[int]"] = None,
             traffic=None) -> Path:
        """Write the versioned artifact: every current specialization's
        plan + pre-transformed weights, the schedule database, and the
        calibrated transform bandwidth.

        ``include_source`` additionally packs the *logical* graph and raw
        weights so the loaded session can specialize unseen batch sizes
        (default: pack whenever the session has them; a frozen session
        saved again has nothing to pack).

        ``buckets`` selects *which* batch-size specializations the
        artifact carries (default ``None``: all current ones).  An
        explicit list specializes and saves exactly those sizes.
        ``buckets="auto"`` closes the measured-traffic loop: the bucket
        set is solved from the recorded arrival histogram
        (:func:`repro.engine.traffic.solve_buckets`) — ``traffic`` may
        be a ``SizeHistogram``, a plain ``{size: count}`` mapping, or a
        ``ServingStats``; default: this session's own ``traffic``
        recorder, filled by the serving driver.  The solved set (and the
        histogram it came from) is written into the manifest's
        ``traffic`` section for provenance."""
        if include_source is None:
            include_source = (self._graph is not None
                              and self._params is not None)
        if include_source and (self._graph is None or self._params is None):
            raise RuntimeError("include_source=True but this session has "
                               "no logical graph/raw weights (loaded from "
                               "a sourceless artifact)")
        chosen, traffic_meta = self._resolve_buckets(buckets, traffic)
        if chosen is not None:
            for b in chosen:
                self.specialize(b)       # no-op for already-bound sizes
        # under the session lock: a serving worker specializing a new
        # batch size mid-save must not change the dict between the weight
        # loop and the manifest (or corrupt either iteration)
        with self._lock:
            return self._save_locked(Path(path), include_source,
                                     only=chosen, traffic_meta=traffic_meta)

    def _resolve_buckets(self, buckets, traffic):
        """Normalize save()'s bucket selection: None (keep all), an
        explicit size list, or "auto" (solve from measured traffic)."""
        if buckets is None:
            if traffic is not None:
                raise ValueError("traffic= is only meaningful with "
                                 "buckets='auto'")
            return None, None
        from repro.engine import traffic as traffic_mod

        if buckets == "auto":
            hist = traffic if traffic is not None else self.traffic
            counts = traffic_mod._coerce_counts(hist)
            if not counts:
                raise ValueError(
                    "buckets='auto' needs recorded traffic: serve some "
                    "requests through AsyncServer (which records arrival "
                    "sizes into session.traffic), or pass traffic= a "
                    "histogram")
            solved = traffic_mod.solve_buckets(counts,
                                               devices=self.devices)
            meta = {"mode": "auto",
                    "histogram": {str(s): c
                                  for s, c in sorted(counts.items())},
                    "buckets": list(solved),
                    "expected_waste": traffic_mod.expected_padded_waste(
                        counts, solved)}
            return sorted(solved), meta
        chosen = sorted({int(b) for b in buckets})
        if not chosen or any(b < 1 for b in chosen):
            raise ValueError(f"buckets must be sizes >= 1, got {buckets}")
        if self.frozen:
            missing = [b for b in chosen if b not in self._specialized]
            if missing:
                raise RuntimeError(
                    f"frozen session cannot specialize buckets {missing} "
                    f"(has {self.batch_sizes})")
        return chosen, {"mode": "explicit", "buckets": chosen}

    def _save_locked(self, path: Path, include_source: bool,
                     only=None, traffic_meta=None) -> Path:
        if not self._specialized:
            raise RuntimeError("nothing to save: session has no "
                               "specializations (call predict/specialize)")
        import shutil

        # build the whole artifact in a sibling temp dir and atomically
        # swap it in: a crash at ANY point of save() leaves either the
        # previous complete artifact or the new complete artifact at
        # `path` — never a half-written mixture.  (This also makes re-save
        # hygiene trivial: stale weight steps / a dropped source dir
        # simply are not in the fresh tree.)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.parent / f".{path.name}.tmp-save"
        if tmp.exists():
            shutil.rmtree(tmp)           # leftover of a crashed save
        tmp.mkdir()
        saved = {batch: m for batch, m in sorted(self._specialized.items())
                 if only is None or batch in only}
        store = CheckpointStore(tmp / "weights")
        for batch, m in saved.items():
            store.save(step=batch, tree=_params_to_flat_ok(m.params),
                       meta={"batch": batch})
        source = None
        if include_source:
            src_store = CheckpointStore(tmp / "source")
            src_store.save(step=0, tree=_params_to_flat_ok(self._params),
                           meta={"kind": "logical-params"})
            source = {
                "graph": _graph_to_json(self._graph),
                # only presets reconstruct exactly; a custom pipeline's
                # loaded session re-plans with the default preset
                "pipeline": (self.pipeline.name
                             if self.pipeline
                             and self.pipeline.name in MODES else None),
                "search_budget": list(self.search_budget),
            }
        plans_dir = tmp / "plans"
        plans_dir.mkdir()
        specs = {}
        for batch, m in saved.items():
            rel = f"plans/batch_{batch:05d}.json"
            (tmp / rel).write_text(json.dumps(_plan_to_json(m.plan)))
            specs[str(batch)] = {"file": rel}
        quantized = None
        if self.dtype == "int8":
            # the payload names the scheme and which convs actually bound
            # int8 codes (the search decides per conv — a mixed plan is
            # normal); written before the checksum walk so it is verified
            # on load like any other file
            (tmp / "quantized.json").write_text(json.dumps({
                "dtype": self.dtype,
                "scheme": ("w8: per-output-channel symmetric int8 weights, "
                           "qmax 127, dequantize scale folded into the "
                           "epilogue scale operand"),
                "schedule_dtypes": {
                    str(batch): {name: s.dtype for name, s in
                                 m.plan.planned.schedules.items()}
                    for batch, m in saved.items()},
            }))
            quantized = {"file": "quantized.json", "dtype": self.dtype}
        manifest = {
            "format": ARTIFACT_FORMAT,
            "version": ARTIFACT_VERSION,
            "model": self.model_name,
            "tuning": self.tuning,
            "transform_bw": self.transform_bw,
            "pipeline": self.pipeline.name if self.pipeline else None,
            "input_spec": {k: list(v) for k, v in self._base_shapes.items()},
            "use_pallas": self.use_pallas,
            "dispatch": self.dispatch,
            "devices": self.devices,
            "specializations": specs,
            "quantized": quantized,
            "source": source,
            # provenance of a learned/filtered bucket set (None for plain
            # saves); load() ignores unknown manifest keys, so older
            # builds read these artifacts fine
            "traffic": traffic_meta,
            # CNN sessions never carry an LM section; the explicit None
            # keeps v5 manifests self-describing (load dispatches on it)
            "lm": None,
            # measured winners only: analytical rankings are re-derivable
            # and would bloat the manifest by megabytes per workload set
            "db": self.db.to_blob(measured_only=True),
            # every file except the manifest itself, verified on load
            "checksums": dir_checksums(tmp),
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if path.exists():
            old = path.parent / f".{path.name}.old-save"
            if old.exists():
                shutil.rmtree(old)
            path.rename(old)
            tmp.rename(path)
            shutil.rmtree(old)
        else:
            tmp.rename(path)
        return path

    @classmethod
    def load(cls, path: Union[str, Path], *,
             dispatch: Optional[str] = None,
             devices: Optional[int] = None) -> "InferenceSession":
        """Reconstruct a session from :meth:`save` output.  No planning,
        no schedule search, no weight transformation happens — the plans
        and physical-layout weights come straight off disk.  Artifacts of
        older versions are upgraded through the migration hook chain;
        future versions are rejected.  If the artifact packs its source
        (v2 ``include_source``), the loaded session is *not* frozen and
        may specialize unseen batch sizes on demand.

        ``devices`` re-targets the artifact to a different device
        count (the scaling benchmark loads *one* artifact at every device
        count).  Plans are built at the per-device sub-batch, so a
        re-targeted load drops the saved specializations and re-plans from
        the packed source — with zero schedule searches whenever the
        artifact's database holds the workloads; it therefore requires a
        source-packed artifact."""
        path = Path(path)
        try:
            raw = (path / "manifest.json").read_text()
        except FileNotFoundError as e:
            raise ArtifactError(
                f"{path} is not a saved artifact: no manifest.json "
                f"({e})") from e
        try:
            manifest = json.loads(raw)
        except json.JSONDecodeError as e:
            raise ArtifactCorruptError(
                f"{path}/manifest.json is corrupt (not valid JSON): {e}"
            ) from e
        if (not isinstance(manifest, dict)
                or manifest.get("format") != ARTIFACT_FORMAT):
            raise ArtifactError(f"{path} is not a {ARTIFACT_FORMAT} "
                                "artifact")
        version = manifest.get("version")
        if not isinstance(version, int) or version > ARTIFACT_VERSION:
            raise ArtifactError(
                f"artifact version {version!r} is newer than this build "
                f"supports ({ARTIFACT_VERSION}); re-save the session with "
                "a matching version")
        while version < ARTIFACT_VERSION:
            hook = _MIGRATIONS.get(version)
            if hook is None:
                raise ArtifactError(
                    f"artifact version {version} has no migration hook to "
                    f"{version + 1}; re-save the session with this build")
            try:
                manifest = hook(manifest, path)
            except (KeyError, TypeError, AttributeError) as e:
                # a structurally-broken old manifest must reject as
                # cleanly as a corrupt current one
                raise ArtifactError(
                    f"artifact manifest is not a valid version {version}: "
                    f"{e!r}") from e
            if manifest.get("version") == version:   # buggy hook guard
                raise ArtifactError(
                    f"migration hook for version {version} did not "
                    "advance the manifest version")
            version = manifest["version"]
        if manifest.get("lm"):
            raise ArtifactError(
                f"{path} is an LM artifact (seq-bucketed prefill + decode "
                "program); load it with repro.engine.LMSession.load")
        # integrity gate: verify every checksummed file BEFORE
        # deserializing anything — a flipped bit in a weight blob or plan
        # is refused typed, never silently served.  Pre-v3 artifacts
        # (checksums migrated to None) load unverified.
        checksums = manifest.get("checksums")
        if isinstance(checksums, dict):
            for rel, want in checksums.items():
                f = path / rel
                if not f.is_file():
                    raise ArtifactCorruptError(
                        f"artifact file {rel} is listed in the manifest "
                        f"checksums but missing from {path} (corrupt or "
                        "partially-copied artifact)")
                got = sha256_file(f)
                if got != want:
                    raise ArtifactCorruptError(
                        f"artifact file {rel} is corrupt: sha256 {got} "
                        f"does not match the manifest's {want}")
        else:
            warnings.warn(
                f"artifact {path} predates checksums (pre-v3) and is "
                "loading UNVERIFIED: its payloads cannot be integrity-"
                "checked.  Re-save the loaded session to backfill "
                "checksums and upgrade it in place.",
                UnverifiedArtifactWarning, stacklevel=2)
        db = ScheduleDatabase()
        db.load_blob(manifest.get("db", {}))
        source = manifest.get("source")
        graph = params = pipeline = None
        if source is not None:
            graph = _graph_from_json(source["graph"])
            try:
                leaves, _, _ = CheckpointStore(
                    path / "source").restore_flat(step=0)
            except (ValueError, FileNotFoundError, KeyError) as e:
                raise ArtifactCorruptError(
                    f"artifact source weights under {path}/source are "
                    f"corrupt or incomplete: {e}") from e
            params = _params_from_flat(leaves)
            pipeline = Pipeline.preset(source.get("pipeline") or "fusion")
        saved_devices = manifest.get("devices", 1)
        retarget = devices is not None and devices != saved_devices
        if retarget and source is None:
            raise ValueError(
                f"artifact was saved at devices={saved_devices} and packs "
                f"no source; cannot re-target to devices={devices} — its "
                "plans embed the per-device sub-batch shapes.  Re-save "
                "with include_source=True")
        sess = cls(graph=graph,
                   base_shapes={k: tuple(v) for k, v in
                                manifest["input_spec"].items()},
                   params=params, pipeline=pipeline, db=db,
                   tuning=manifest["tuning"],
                   transform_bw=manifest.get("transform_bw"),
                   search_budget=tuple(
                       (source or {}).get("search_budget", (6, 2, 3))),
                   use_pallas=manifest.get("use_pallas", False),
                   dispatch=dispatch or manifest.get("dispatch", "whole"),
                   devices=devices if retarget else saved_devices,
                   dtype=(manifest.get("quantized") or {}).get("dtype",
                                                               "fp32"),
                   model_name=manifest.get("model"))
        if retarget:
            # saved plans are per-device-sub-batch-shaped for the *old*
            # device count; re-specialize from the packed source instead
            return sess
        store = CheckpointStore(path / "weights")
        specs = manifest.get("specializations")
        if not isinstance(specs, dict):
            raise ArtifactCorruptError(
                f"{path} manifest has no specializations table (corrupt "
                "artifact)")
        for bstr, plan_js in specs.items():
            batch = int(bstr)
            if isinstance(plan_js, dict) and set(plan_js) == {"file"}:
                # v3: plan stored as an external per-batch file (already
                # checksum-verified above when the manifest carries sums)
                try:
                    plan_js = json.loads((path / plan_js["file"])
                                         .read_text())
                except FileNotFoundError as e:
                    raise ArtifactCorruptError(
                        f"artifact plan for batch {batch} is missing: "
                        f"{e}") from e
                except json.JSONDecodeError as e:
                    raise ArtifactCorruptError(
                        f"artifact plan for batch {batch} is corrupt "
                        f"(not valid JSON): {e}") from e
            try:
                plan = _plan_from_json(plan_js)
                leaves, _, _ = store.restore_flat(step=batch)
            except (ValueError, FileNotFoundError, KeyError) as e:
                raise ArtifactCorruptError(
                    f"artifact specialization for batch {batch} is "
                    f"corrupt or incomplete: {e}") from e
            sess._specialized[batch] = CompiledModel(
                plan=plan,
                params=_params_from_flat(leaves),
                use_pallas=sess.use_pallas, interpret=sess.interpret,
                dispatch=sess.dispatch, devices=sess.devices)
        return sess


# Short alias used throughout the docs: Session.load(path).predict(x)
Session = InferenceSession


# ---------------------------------------------------------------------------
# compile(): the public front door
# ---------------------------------------------------------------------------

def compile(model: Union[str, Graph],                     # noqa: A001
            input_spec: Union[Dict[str, Tuple[int, ...]],
                              Tuple[int, ...], None] = None, *,
            params: Optional[Params] = None,
            tuning: str = "roofline",
            pipeline: Optional[Pipeline] = None,
            db: Union[ScheduleDatabase, str, Path, None] = None,
            transform_bw: Optional[float] = None,
            search_budget: Tuple[int, int, int] = (6, 2, 3),
            seed: int = 0,
            use_pallas: bool = False, interpret: Optional[bool] = None,
            dispatch: str = "whole", devices: int = 1,
            dtype: str = "fp32",
            eager: bool = True) -> InferenceSession:
    """Build an :class:`InferenceSession` for a model.

    model       zoo name (``"resnet-18"``) or a ``core.graph.Graph``
    input_spec  ``{input_name: NCHW shape}``, or a single NCHW tuple for
                one-input models (zoo names may omit it for the builder's
                default resolution)
    tuning      "roofline" — analytical schedule ranking (default);
                "cached"   — reuse whatever the schedule database already
                             holds (e.g. measured winners from a benchmark
                             run or a loaded artifact), analytical for
                             misses, never measures;
                "measured" — the guided wall-clock search on this host,
                             with ``transform_bw`` auto-calibrated from a
                             one-shot host-copy probe
    pipeline    a ``core.pipeline.Pipeline``; default is the full ladder
                (``Pipeline.preset("fusion")``)
    db          schedule database instance or path to a persisted one
    use_pallas  run blocked convs through the Pallas kernel instead of the
                jnp templates; ``interpret`` (default None) compiles it
                on TPU and interprets it elsewhere
    devices     batch-shard every specialization over the first this many
                of ``jax.devices()`` (``jax.shard_map`` over a 1-D data
                mesh).  Batch sizes must divide by
                it — sharding composes *above* the per-core NCHW[x]c
                templates, so ``candidate_schedules`` is unchanged and
                each device runs the plan built for its B/devices
                sub-batch
    dtype       "fp32" (default), or "int8": enumerate per-output-channel
                W8-quantized schedules alongside fp32 ones; the search
                picks per conv, weights quantize once at bind time, and
                the dequantize scale folds into the fused epilogue like a
                BN scale.  Saved artifacts carry a checksummed
                ``quantized.json`` payload
    eager       plan + bind the input_spec's batch size now (default); the
                session still specializes other batch sizes on demand
    """
    from repro.models.cnn import build as build_zoo

    # LM dispatch: an LMConfig (or assigned-LM-architecture name) routes
    # to the LM arm — one compiler front door, two workload families.
    # input_spec is then the (batch, max_len) token shape.
    from repro.models.lm import LMConfig as _LMConfig
    lm_model = None
    if isinstance(model, _LMConfig):
        lm_model = model
    elif isinstance(model, str):
        from repro.configs import ARCHS as _LM_ARCHS
        if model in _LM_ARCHS:
            lm_model = model
    if lm_model is not None:
        from repro.engine.lm_session import compile_lm
        spec = input_spec
        if isinstance(spec, dict):
            if len(spec) != 1:
                raise ValueError("LM models take exactly one token input; "
                                 f"got spec keys {sorted(spec)}")
            (spec,) = spec.values()
        if spec is None or len(tuple(spec)) != 2:
            raise ValueError(
                "compile(<LM model>, ...) needs input_spec as the "
                f"(batch, max_len) token shape; got {input_spec!r}")
        b, max_len = (int(v) for v in spec)
        return compile_lm(lm_model, max_len=max_len, batch=b, seed=seed,
                          params=params)

    if isinstance(model, Graph):
        if not isinstance(input_spec, dict):
            raise ValueError("compile(Graph, ...) needs input_spec as a "
                             "{input_name: shape} dict")
        graph, shapes = model, {k: tuple(v) for k, v in input_spec.items()}
        model_name = None
    else:
        model_name = model
        if input_spec is None:
            graph, shapes = build_zoo(model_name)
        else:
            if isinstance(input_spec, dict):
                if len(input_spec) != 1:
                    raise ValueError(
                        f"zoo models take exactly one input; got spec keys "
                        f"{sorted(input_spec)} — pass a Graph for "
                        "multi-input models")
                (shape,) = (tuple(v) for v in input_spec.values())
            else:
                shape = tuple(input_spec)
            if len(shape) != 4:
                raise ValueError(f"expected an NCHW shape, got {shape}")
            # the zoo builders are parameterized by (batch, image) only —
            # reject specs they cannot honor instead of silently building
            # a model the caller's input will not fit
            if shape[1] != 3 or shape[2] != shape[3]:
                raise ValueError(
                    f"zoo models take square RGB inputs (N, 3, S, S); got "
                    f"{shape} — build the graph yourself for other shapes")
            graph, shapes = build_zoo(model_name, batch=shape[0],
                                      image=shape[2])
    if isinstance(db, (str, Path)):
        db = ScheduleDatabase(db)
        # read-only snapshot: the session persists its database inside the
        # artifact; cache misses must not rewrite the source file (a
        # roofline fallback would bloat a measured-winners db)
        db.path = None
    if params is None:
        params = init_params(graph, shapes, seed=seed)
    sess = InferenceSession(
        graph=graph, base_shapes=shapes, params=params,
        pipeline=pipeline or Pipeline.preset("fusion"), db=db,
        tuning=tuning, transform_bw=transform_bw,
        search_budget=search_budget, use_pallas=use_pallas,
        interpret=interpret, dispatch=dispatch, devices=devices,
        dtype=dtype, model_name=model_name)
    if eager:
        base = next(iter(shapes.values()))[0]
        if devices > 1 and base % devices:
            raise ValueError(
                f"input_spec batch {base} is not divisible by devices="
                f"{devices}; pass a divisible batch (or eager=False and "
                "specialize divisible buckets yourself)")
        sess.specialize(base)
    return sess

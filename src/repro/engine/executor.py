"""Inference engine: planned graph -> jitted executable.

Binding a ``Plan`` to parameters performs §3.2's compile-time weight
transformation once — conv kernels to ``KCRS[x]c[y]k``, BN vectors to the
blocked broadcast shape — then the forward pass executes the rewritten
graph with zero runtime weight relayouts.  For fused ``conv_block`` nodes
(§3.1 operation fusion) binding also folds the absorbed BatchNorm into the
conv: the scale multiplies the kernel's output channels and the shift
becomes the block's bias-like epilogue vector, so the fused kernel runs a
pure conv + shift + (residual) + ReLU epilogue.  The forward function is
jitted with the (pre-transformed) params as a traced argument, so weight
updates don't recompile.

Each conv node executes under its planned ``ConvSchedule`` — including the
lowering ``variant`` (per_tap / tap_stack / scan / patch_gemm / xla_conv) the
search picked for its workload; the schedule rides into
``kernels.ops.conv2d_blocked`` / ``conv2d_block_blocked`` which dispatch
the jnp template accordingly (the Pallas path has a single VMEM-resident
loop nest and ignores the variant axis).

Two dispatch modes:

* ``"whole"`` (default) — one ``jax.jit`` over the full graph walk; XLA
  sees the entire model.
* ``"op"``    — classic graph-runtime dispatch: every node is its own
  jitted executable and intermediates materialize between nodes, the
  execution model of the paper's TVM/MXNet baselines.  This is the mode
  where graph-level fusion is measured (benchmarks/fusion_ablation.py):
  a fused plan dispatches one kernel where the unfused plan dispatches
  conv + BN + add + ReLU.

Multi-device execution (two orthogonal levers over ``jax.devices()`` —
the chips of a TPU host, or forced host CPU devices on a CPU-only machine):

* ``devices=D`` — **intra-op** data parallelism: the whole-graph forward
  is wrapped in ``jax.shard_map`` over a 1-D ``("data",)`` mesh of the
  first D devices, splitting the batch axis so every device runs the
  *same* NCHW[x]c program on a B/D sub-batch (the plan is built at the
  sub-batch shape; sharding composes *above* the templates).  Parameters
  are replicated once at bind.  Batches must divide by D.
* :meth:`CompiledModel.replica` — **inter-op** replicas: the same
  executable with its parameters committed to another device, so
  concurrent serving workers execute on distinct devices (one program
  copy per device, compiled lazily on first use; the same program runs on
  every device of one kind, so the serving bit-identical guarantee holds
  per fixed (bucket, device-count) program regardless of which worker
  ran the batch).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epilogue import EpilogueSpec, PoolSpec, fold_dequant_scale
from repro.core.layout import Layout, NCHW, kernel_to_kcrs_ck
from repro.core.pipeline import Plan
from repro.core.quantize import quantize_per_channel
from repro.kernels.ops import prelay_patch_gemm_weight
from repro.nn import ops
from repro.nn.init import Params


def _patch_gemm_prelaid(schedule, layout: Layout, use_pallas: bool) -> bool:
    """Whether this conv's weight is stored panel-major at bind time: the
    jnp patch_gemm lowering is the only consumer of the pre-laid form (the
    Pallas kernel keeps KCRS[x]c[y]k).  Used identically by ``bind_params``
    (to transform once) and the dispatchers (to tell the kernel what
    arrived)."""
    return (not use_pallas and schedule is not None and layout.is_blocked
            and schedule.resolved_variant() == "patch_gemm")


def _block_channel_vec(v: jnp.ndarray, layout: Layout) -> jnp.ndarray:
    c = v.shape[0]
    if layout.is_blocked:
        x = layout.block
        return v.reshape(c // x, x)[:, None, None, :]      # (C//x, 1, 1, x)
    return v[:, None, None]                                # (C, 1, 1)


def _bind_conv_block(plan: Plan, node, params: Params,
                     fold_bn: bool, use_pallas: bool) -> Dict[str, jnp.ndarray]:
    """Fused-block binding: conv weight/bias under the block's own name,
    the absorbed BN's scale/shift under ``attrs["bn_from"]``.  With
    ``fold_bn`` (the default — conv weights are static at bind time) the
    scale is multiplied into the kernel's output channels and only the
    shift survives as an epilogue vector."""
    p_conv = params[node.name]
    w = p_conv["w"]
    scale: Optional[jnp.ndarray] = None
    shift: Optional[jnp.ndarray] = None
    if "b" in p_conv:
        shift = p_conv["b"].astype(jnp.float32)
    bn_from = node.attrs.get("bn_from")
    if bn_from is not None:
        p_bn = params[bn_from]
        s = p_bn["scale"].astype(jnp.float32)
        t = p_bn["shift"].astype(jnp.float32)
        # bn(conv(x) + b) = conv(x) * s + (b * s + t)
        shift = t if shift is None else shift * s + t
        scale = s
    if fold_bn and scale is not None:
        w = (w.astype(jnp.float32)
             * scale[:, None, None, None]).astype(w.dtype)
        scale = None

    lay = plan.planned.layouts[node.name]
    sched = plan.planned.schedules.get(node.name)
    if (sched is not None and lay.is_blocked
            and getattr(sched, "dtype", "fp32") == "int8"):
        # §3.2 extended to numerics: the weight transformation pass is
        # also where quantization happens — per-output-channel symmetric
        # int8 codes replace the fp32 kernel (after any BN fold, so the
        # codes absorb the BN scale), and the dequantize scale folds into
        # the epilogue's per-channel scale exactly like an unfolded BN.
        wq, w_scale = quantize_per_channel(np.asarray(w), axis=0)
        w = jnp.asarray(wq)
        scale = fold_dequant_scale(scale, w_scale)
    q: Dict[str, jnp.ndarray] = {}
    if sched is not None and lay.is_blocked:
        q["w"] = kernel_to_kcrs_ck(w, sched.ic_bn, sched.oc_bn)
        if _patch_gemm_prelaid(sched, lay, use_pallas):
            q["w"] = prelay_patch_gemm_weight(q["w"])

        def blk(v):
            return v.reshape(v.shape[0] // sched.oc_bn, sched.oc_bn)
    else:
        q["w"] = w

        def blk(v):
            return v[:, None, None]
    if scale is not None:
        q["scale"] = blk(scale)
    if shift is not None:
        q["shift"] = blk(shift)
    return q


def bind_params(plan: Plan, params: Params, fold_bn: bool = True,
                use_pallas: bool = False) -> Params:
    """Pre-transform logical parameters to the plan's physical layouts.
    Weights of convs scheduled on the jnp ``patch_gemm`` lowering are
    additionally pre-laid to panel-major order (``w_prelaid``), so the
    kernel's runtime weight transpose disappears."""
    g = plan.planned.graph
    out: Params = {}
    consumed = set()
    for node in g.topo_order():
        if node.op != "conv_block":
            continue
        out[node.name] = _bind_conv_block(plan, node, params, fold_bn,
                                          use_pallas)
        consumed.add(node.name)
        if node.attrs.get("bn_from") is not None:
            consumed.add(node.attrs["bn_from"])
    for name, p in params.items():
        if name in consumed:
            continue
        node = g.nodes.get(name)
        if node is None:       # node was renamed/removed by the rewrite
            out[name] = dict(p)
            continue
        lay = plan.planned.layouts[name]
        if node.op == "conv2d" and name in plan.planned.schedules:
            s = plan.planned.schedules[name]
            q = {"w": kernel_to_kcrs_ck(p["w"], s.ic_bn, s.oc_bn)}
            if _patch_gemm_prelaid(s, lay, use_pallas):
                q["w"] = prelay_patch_gemm_weight(q["w"])
            if "b" in p:
                q["b"] = _block_channel_vec(p["b"], lay)
            out[name] = q
        elif node.op == "conv2d":
            q = {"w": p["w"]}
            if "b" in p:
                q["b"] = _block_channel_vec(p["b"], NCHW)
            out[name] = q
        elif node.op == "batch_norm":
            out[name] = {"scale": _block_channel_vec(p["scale"], lay),
                         "shift": _block_channel_vec(p["shift"], lay)}
        else:
            out[name] = dict(p)
    return out


def _eval_node(node, lay: Layout, schedule, use_pallas: bool,
               interpret: Optional[bool], p: Dict[str, jnp.ndarray],
               *ins: jnp.ndarray) -> jnp.ndarray:
    """One graph node on already-computed inputs — shared by both dispatch
    modes (the whole-graph jit and the per-node graph-runtime path)."""
    a = node.attrs
    if node.op == "conv2d":
        ph = a.get("pad", 0)
        pw = a.get("pad_w", -1)
        return ops.conv2d(
            ins[0], p["w"], p.get("b"), lay,
            stride=a.get("stride", 1),
            pad=ph if pw < 0 else (ph, pw),
            groups=a.get("groups", 1),
            schedule=schedule,
            use_pallas=use_pallas, interpret=interpret,
            w_prelaid=_patch_gemm_prelaid(schedule, lay, use_pallas))
    if node.op == "conv_block":
        ph = a.get("pad", 0)
        pw = a.get("pad_w", -1)
        # inputs: [data, residual?, concat_buf?] — buffer last when fused
        concat_into = bool(a.get("concat_into"))
        out_buf = ins[-1] if concat_into else None
        n_extra = len(ins) - 1 - (1 if concat_into else 0)
        residual = ins[1] if n_extra >= 1 else None
        pool = None
        if a.get("pool_kind"):
            pool = PoolSpec(a["pool_kind"], a["pool_k"], a["pool_stride"],
                            a.get("pool_pad", 0),
                            bool(a.get("pool_ceil", False)))
        spec = EpilogueSpec(
            relu=bool(a.get("relu")), pool=pool,
            concat_offset=a.get("concat_offset", 0) if concat_into else 0,
            concat_total=a.get("concat_total", 0) if concat_into else 0)
        return ops.conv_block(
            ins[0], p["w"], p.get("scale"), p.get("shift"),
            residual, lay,
            stride=a.get("stride", 1),
            pad=ph if pw < 0 else (ph, pw),
            groups=a.get("groups", 1), epilogue=spec, out_buf=out_buf,
            schedule=schedule,
            use_pallas=use_pallas, interpret=interpret,
            w_prelaid=_patch_gemm_prelaid(schedule, lay, use_pallas))
    if node.op == "batch_norm":
        return ops.batch_norm(ins[0], p["scale"], p["shift"], lay)
    if node.op == "relu":
        return ops.relu(ins[0])
    if node.op == "softmax":
        return ops.softmax(ins[0], lay)
    if node.op == "l2_normalize":
        return ops.l2_normalize(ins[0], lay)
    if node.op == "max_pool":
        return ops.max_pool(ins[0], a["k"], a.get("stride", a["k"]),
                            a.get("pad", 0), a.get("ceil_mode", False))
    if node.op == "avg_pool":
        return ops.avg_pool(ins[0], a["k"], a.get("stride", a["k"]),
                            a.get("pad", 0), a.get("ceil_mode", False))
    if node.op == "global_avg_pool":
        return ops.global_avg_pool(ins[0])
    if node.op == "add":
        return ops.add(*ins)
    if node.op == "concat":
        return ops.concat(list(ins), lay)
    if node.op == "concat_alloc":
        return ops.concat_alloc(list(ins), a["offsets"],
                                a["total_channels"], lay)
    if node.op == "flatten":
        return ops.flatten(ins[0])
    if node.op == "reshape":
        return ins[0].reshape(a["shape"])
    if node.op == "dense":
        return ops.dense(ins[0], p["w"], p.get("b"))
    if node.op == "layout_transform":
        return ops.layout_transform(ins[0], a["src_layout"], a["dst_layout"])
    raise NotImplementedError(node.op)


def _scoped(name: str, fn):
    """``fn`` traced inside ``jax.named_scope(name)``: trace-time metadata
    only, so it holds under ``jax.jit`` in both dispatch modes."""
    def call(*args):
        with jax.named_scope(name):
            return fn(*args)
    return call


def _device_mesh(devices: int):
    """1-D ("data",) mesh over the first ``devices`` of ``jax.devices()``;
    fails when the process sees fewer."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < devices:
        raise RuntimeError(
            f"plan wants {devices} devices but this process sees "
            f"{len(devs)} ({devs[0].platform}); compile with devices <= "
            f"{len(devs)}")
    return Mesh(np.asarray(devs[:devices]), ("data",))


@dataclasses.dataclass
class CompiledModel:
    """Callable end-to-end executable for one plan.  ``devices > 1``
    executes batch-sharded over a device mesh (see module docs)."""

    plan: Plan
    params: Params               # pre-transformed (bind_params output)
    use_pallas: bool = False
    interpret: Optional[bool] = None   # None: compiled on TPU only
    dispatch: str = "whole"      # "whole" (one jit) | "op" (per-node jit)
    devices: int = 1             # batch-sharded over this many devices

    def __post_init__(self):
        structure = self.plan.planned
        use_pallas, interpret = self.use_pallas, self.interpret
        topo = structure.graph.topo_order()
        self._replicas: Dict[Any, "_DeviceReplica"] = {}
        self._warmed: set = set()        # (device, input shape, dtype)
        self._warm_lock = threading.Lock()

        if self.dispatch not in ("whole", "op"):
            raise ValueError(f"unknown dispatch mode {self.dispatch!r}")
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.devices > 1 and self.dispatch != "whole":
            raise ValueError("sharded execution (devices > 1) requires "
                             "whole-graph dispatch; per-node dispatch "
                             "would materialize every intermediate "
                             "across the mesh")
        # each node's operations carry its name (``jax.named_scope``) in
        # their HLO ``op_name``, so a device trace can be read per node
        fns = {n.name: _scoped(n.name, functools.partial(
                   _eval_node, n, structure.layouts[n.name],
                   structure.schedules.get(n.name), use_pallas, interpret))
               for n in topo if n.op != "input"}
        if self.dispatch == "op":
            # graph-runtime dispatch: one XLA executable per node, compiled
            # once, intermediates materialized between dispatches
            fns = {name: jax.jit(f) for name, f in fns.items()}

        def forward(params: Params, inputs: Dict[str, jnp.ndarray]):
            env: Dict[str, jnp.ndarray] = {}
            for node in topo:
                if node.op == "input":
                    env[node.name] = inputs[node.name]
                    continue
                env[node.name] = fns[node.name](
                    params.get(node.name, {}),
                    *[env[i] for i in node.inputs])
            outs = [env[o] for o in structure.graph.outputs]
            return outs[0] if len(outs) == 1 else tuple(outs)

        if self.devices > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = _device_mesh(self.devices)
            self._mesh = mesh
            # params replicated (P()), every input/output batch-sharded
            # (P("data") partitions the leading axis); check_vma off so
            # Pallas calls inside the forward stay legal per-shard
            sharded = jax.shard_map(forward, mesh=mesh,
                                    in_specs=(P(), P("data")),
                                    out_specs=P("data"), check_vma=False)
            self._forward = jax.jit(sharded)
            # replicate once at bind, not per call
            self.params = jax.device_put(
                self.params, NamedSharding(mesh, P()))
        else:
            self._mesh = None
            self._forward = jax.jit(forward) if self.dispatch == "whole" \
                else forward

    def _check_batch(self, inputs: Dict[str, jnp.ndarray]) -> None:
        if self.devices <= 1:
            return
        for name, v in inputs.items():
            if v.shape[0] % self.devices:
                raise ValueError(
                    f"input {name!r} batch {v.shape[0]} is not divisible "
                    f"by devices={self.devices}; sharded programs need an "
                    "equal per-device sub-batch")

    def __call__(self, inputs: Dict[str, jnp.ndarray]):
        self._check_batch(inputs)
        return self._forward(self.params, inputs)

    def predict(self, x: jnp.ndarray):
        """Single-input convenience (the common CNN case)."""
        return self(inputs={self.input_name: x})

    @property
    def input_name(self) -> str:
        (inp,) = [n.name for n in self.plan.planned.graph.topo_order()
                  if n.op == "input"]
        return inp

    def replica(self, device=None) -> "CompiledModel | _DeviceReplica":
        """The same program with parameters resident on ``device`` — the
        inter-op serving replica (each ``AsyncServer`` worker executes on
        its own device).  Shares this model's jitted forward: JAX
        dispatches on the committed parameters' device, compiling one
        executable per device lazily.  Sharded models (``devices > 1``)
        already span the mesh and return ``self``."""
        if device is None or self.devices > 1:
            return self
        # keyed by the device itself: ids repeat across platforms
        rep = self._replicas.get(device)
        if rep is None:
            rep = _DeviceReplica(self, device)
            self._replicas[device] = rep
        return rep

    def warm_replicas(self, devices: Sequence, x: jnp.ndarray) -> None:
        """Compiles the replicas on ``devices`` for inputs like ``x`` all at
        once, one thread a device, by running each on ``x``: XLA compiles
        with the interpreter lock released, so N replicas cost about the
        time of one where each would otherwise compile on its first
        batch.  Devices already warmed for ``x``'s shape are skipped; a
        single one is left to compile on its first call."""
        key = (tuple(x.shape), str(x.dtype))
        if all((d, key) in self._warmed for d in devices):
            return
        with self._warm_lock:
            reps = [self.replica(d) for d in dict.fromkeys(devices)
                    if (d, key) not in self._warmed]
            if len(reps) > 1:
                with ThreadPoolExecutor(len(reps)) as pool:
                    list(pool.map(lambda r: jax.block_until_ready(
                        r.predict(x)), reps))
            self._warmed.update((d, key) for d in devices)


class _DeviceReplica:
    """One ``CompiledModel`` executing on a specific device (shared
    jitted forward, device-committed parameter copy)."""

    def __init__(self, model: CompiledModel, device) -> None:
        self.model = model
        self.device = device
        self.plan = model.plan
        self.params = jax.device_put(model.params, device)

    def __call__(self, inputs: Dict[str, jnp.ndarray]):
        return self.model._forward(self.params, inputs)

    def predict(self, x: jnp.ndarray):
        return self(inputs={self.model.input_name: x})


def compile_model(plan: Plan, params: Params, use_pallas: bool = False,
                  interpret: Optional[bool] = None, fold_bn: bool = True,
                  dispatch: str = "whole", devices: int = 1) -> CompiledModel:
    bound = bind_params(plan, params, fold_bn=fold_bn, use_pallas=use_pallas)
    return CompiledModel(plan=plan, params=bound, use_pallas=use_pallas,
                         interpret=interpret, dispatch=dispatch,
                         devices=devices)

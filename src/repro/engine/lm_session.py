"""LM inference sessions: seq-bucketed prefill + one decode program.

The LM mirror of ``engine/session.py``'s CNN sessions.  A CNN session
specializes per *batch size*; an LM session specializes prefill per
*sequence-length bucket* and owns a single decode program (position is a
traced scalar, so every decode step of every request runs the same
executable).  The bucket set comes from measured prompt-length traffic
through :func:`repro.engine.traffic.solve_seq_buckets` — the same exact
DP the batch buckets use, reflected, because prefill buckets truncate
*down*: right-padding a prompt would corrupt recurrent state (SSM / RG-LRU
layers) and windowed KV rings, so a prompt prefills the largest bucket
``<=`` its length and catches the leftover tokens up through the decode
program, one step each.

``generate`` is greedy (argmax) decode with an optional ``on_token``
callback — the hook ``AsyncServer.submit_stream`` streams tokens through —
and, with ``keep_logits``, each step's logits left on the device.  Each
phase is a ``jax.profiler`` span on the host (``lm.prefill`` with its
``bucket`` and ``prompt_len``, ``lm.catchup`` and ``lm.decode`` with their
``steps``), and the two jitted programs put every device op under the
``jax.named_scope`` of its phase (``lm.prefill``, ``lm.decode``), so a
profiler trace attributes device time to phases.  ``counters()`` counts
the work done (``COUNTERS``) for readers that take a delta over a
window.
Streaming is observational: the callback sees exactly the tokens the
returned array holds, so streamed and unstreamed decode are bit-identical
by construction, and the serving layer's watchdog may re-execute a
generation idempotently.

Artifacts are version-5 ``neocpu-inference-session`` directories whose
manifest carries an ``"lm"`` section instead of a specializations table:
config + bucket set + traffic provenance in the manifest, raw weights in
a ``CheckpointStore``, everything checksummed, written with the same
atomic tmp-dir swap.  ``load -> generate`` replays zero schedule searches
(prefill programs re-jit per bucket on first use; nothing is searched).
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import threading
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.store import CheckpointStore, dir_checksums, sha256_file
from repro.engine.telemetry import SizeHistogram
from repro.engine.traffic import (_coerce_counts, expected_catchup_tokens,
                                  solve_seq_buckets)
from repro.models.lm import (LMConfig, decode_step, init_cache, init_params,
                             prefill)

__all__ = ["LMSession", "compile_lm"]

# requests generated; prompt tokens in them; tokens prefilled (the
# buckets); decode steps that caught a prompt's tail up, and all decode
# steps (catch-up included); tokens generated
COUNTERS = ("requests", "prompt_tokens", "prefill_tokens", "catchup_steps",
            "decode_steps", "generated_tokens")


def _lm_archs() -> Dict[str, LMConfig]:
    from repro.configs import ARCHS
    return ARCHS


class LMSession:
    """A compiled LM: params bound, prefill jitted per seq bucket, one
    jitted decode program.  Thread-safe the way ``InferenceSession`` is:
    program construction happens under a lock; jitted calls run outside
    it."""

    def __init__(self, cfg: LMConfig, params, *, max_len: int,
                 batch: int = 1,
                 seq_buckets: Sequence[int] = (),
                 model_name: Optional[str] = None) -> None:
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        buckets = sorted({int(b) for b in seq_buckets})
        if any(b < 1 or b > max_len for b in buckets):
            raise ValueError(f"seq_buckets must lie in [1, max_len="
                             f"{max_len}], got {seq_buckets}")
        self.cfg = cfg
        self.max_len = int(max_len)
        self.batch = int(batch)
        self.seq_buckets = buckets
        self.model_name = model_name or cfg.name
        self.traffic = SizeHistogram()        # prompt lengths, not rows
        self._params = params
        self._lock = threading.RLock()
        self._prefill_progs: Dict[int, Callable] = {}
        self._decode_prog: Optional[Callable] = None
        self._counts = dict.fromkeys(COUNTERS, 0)
        self._counts_lock = threading.Lock()

    @property
    def params(self):
        """The bound parameters, as the programs take them."""
        return self._params

    # -- the surface AsyncServer speaks --------------------------------------
    @property
    def input_spec(self) -> Dict[str, tuple]:
        return {"tokens": (self.batch, self.max_len)}

    @property
    def frozen(self) -> bool:
        # the batch dimension is fixed at compile time (decode caches are
        # allocated per batch); seq buckets are the flexible axis
        return True

    @property
    def batch_sizes(self):
        return [self.batch]

    # -- programs -------------------------------------------------------------
    def _prefill_for(self, bucket: int) -> Callable:
        with self._lock:
            fn = self._prefill_progs.get(bucket)
            if fn is None:
                cfg, max_len = self.cfg, self.max_len

                def lm_prefill(params, toks):
                    with jax.named_scope("lm.prefill"):
                        return prefill(params, cfg, toks, max_len=max_len)

                fn = jax.jit(lm_prefill)
                self._prefill_progs[bucket] = fn
        return fn

    def _decode(self) -> Callable:
        with self._lock:
            if self._decode_prog is None:
                cfg = self.cfg

                def lm_decode(params, token, cache, pos):
                    with jax.named_scope("lm.decode"):
                        return decode_step(params, cfg, token, cache, pos)

                self._decode_prog = jax.jit(lm_decode)
        return self._decode_prog

    def counters(self) -> Dict[str, int]:
        """The work done since construction, by ``COUNTERS`` name."""
        with self._counts_lock:
            return dict(self._counts)

    def bucket_for(self, prompt_len: int) -> Optional[int]:
        """Largest seq bucket ``<=`` the prompt length, or None (the
        prompt runs entirely through the decode program)."""
        under = [b for b in self.seq_buckets if b <= prompt_len]
        return max(under) if under else None

    def prewarm(self) -> None:
        """Compile every bucket's prefill program and the decode program
        up front (serving wants no first-request compile stall)."""
        dummy = jnp.zeros((self.batch, 1), jnp.int32)
        dec = self._decode()
        cache = init_cache(self.cfg, self.batch, self.max_len)
        jax.block_until_ready(dec(self._params, dummy, cache,
                                  jnp.int32(0))[0])
        for b in self.seq_buckets:
            toks = jnp.zeros((self.batch, b), jnp.int32)
            jax.block_until_ready(
                self._prefill_for(b)(self._params, toks)[1])

    # -- generation ------------------------------------------------------------
    def generate(self, tokens, max_new_tokens: int, *,
                 on_token: Optional[Callable[[int, np.ndarray], None]] = None,
                 keep_logits: bool = False):
        """Greedy decode: returns the ``(batch, max_new_tokens)`` int32
        token array.  ``on_token(step, tokens_b)`` fires as each step's
        tokens become available — the streaming hook; it observes the
        exact values the return array holds (bit-identical by
        construction) and duplicate replays of already-emitted steps are
        the *caller's* concern (``TokenStream`` dedups by step index, so
        a watchdog-requeued generation is idempotent).  With
        ``keep_logits`` it returns ``(tokens, logits)`` instead, where
        ``logits[t]`` is the ``(batch, vocab)`` device array the step-``t``
        tokens were taken from; keeping them moves nothing to the host."""
        # the prompt stays on the host: a slice of it per prefill bucket or
        # catch-up position would otherwise compile a program per position
        toks = np.asarray(tokens)
        if toks.ndim != 2 or toks.shape[0] != self.batch:
            raise ValueError(
                f"tokens must be ({self.batch}, prompt_len), got "
                f"{tuple(toks.shape)}")
        if not np.issubdtype(toks.dtype, np.integer):
            raise ValueError(f"tokens must be integers, got {toks.dtype}")
        toks = toks.astype(np.int32)
        prompt_len = int(toks.shape[1])
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if prompt_len + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + new tokens ({max_new_tokens}) "
                f"overflow max_len={self.max_len}")
        # prompt-length traffic is recorded at *submission* (AsyncServer
        # .submit_stream), not here: a watchdog-requeued generation
        # re-executes, and demand must count once per request
        dec = self._decode()
        bucket = self.bucket_for(prompt_len)
        start = bucket or 0
        with TraceAnnotation("lm.prefill", bucket=start,
                             prompt_len=prompt_len):
            if bucket is None:
                # below every bucket: run the whole prompt through decode
                cache = init_cache(self.cfg, self.batch, self.max_len)
                logits = None
            else:
                cache, logits = self._prefill_for(bucket)(
                    self._params, toks[:, :bucket])
        with TraceAnnotation("lm.catchup", steps=prompt_len - start):
            for p in range(start, prompt_len):
                logits, cache = dec(self._params, toks[:, p:p + 1], cache,
                                    jnp.int32(p))
        out, kept = [], []
        with TraceAnnotation("lm.decode", steps=max_new_tokens - 1):
            for t in range(max_new_tokens):
                if keep_logits:
                    kept.append(logits)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                step = np.asarray(nxt)
                out.append(step)
                if on_token is not None:
                    on_token(t, step)
                if t + 1 < max_new_tokens:       # advance for the next token
                    logits, cache = dec(self._params, nxt[:, None], cache,
                                        jnp.int32(prompt_len + t))
        done = {"requests": 1, "prompt_tokens": prompt_len,
                "prefill_tokens": start,
                "catchup_steps": prompt_len - start,
                "decode_steps": prompt_len - start + max_new_tokens - 1,
                "generated_tokens": max_new_tokens}
        with self._counts_lock:
            for k, v in done.items():
                self._counts[k] += v
        tokens_out = np.stack(out, axis=1)
        return (tokens_out, kept) if keep_logits else tokens_out

    # -- persistence -----------------------------------------------------------
    def save(self, path: Union[str, Path]) -> Path:
        """Write the version-5 LM artifact: manifest (config, bucket set,
        traffic provenance) + checksummed raw weights, via the same
        atomic tmp-dir swap CNN artifacts use."""
        from repro.engine.session import ARTIFACT_FORMAT, ARTIFACT_VERSION

        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.parent / f".{path.name}.tmp-save"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir()
        CheckpointStore(tmp / "weights").save(
            step=0, tree=self._params, meta={"kind": "lm-params"})
        hist = dict(self.traffic.counts()) if hasattr(self.traffic,
                                                      "counts") else {}
        manifest = {
            "format": ARTIFACT_FORMAT,
            "version": ARTIFACT_VERSION,
            "model": self.model_name,
            "lm": {
                "config": dataclasses.asdict(self.cfg),
                "max_len": self.max_len,
                "batch": self.batch,
                "seq_buckets": list(self.seq_buckets),
                "traffic": {"histogram": {str(s): c for s, c in
                                          sorted(hist.items())}},
            },
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
    def load(cls, path: Union[str, Path]) -> "LMSession":
        """Reconstruct an LM session from :meth:`save` output: checksums
        verified before deserialization, zero schedule searches, ready to
        ``generate`` through the saved bucket set."""
        from repro.engine.session import (ARTIFACT_FORMAT, ARTIFACT_VERSION,
                                          ArtifactCorruptError,
                                          ArtifactError)

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
                f"supports ({ARTIFACT_VERSION})")
        lm = manifest.get("lm")
        if not lm:
            raise ArtifactError(
                f"{path} is a CNN artifact (no 'lm' section); load it "
                "with InferenceSession.load")
        checksums = manifest.get("checksums")
        if isinstance(checksums, dict):
            for rel, want in checksums.items():
                f = path / rel
                if not f.is_file():
                    raise ArtifactCorruptError(
                        f"artifact file {rel} is listed in the manifest "
                        f"checksums but missing from {path}")
                got = sha256_file(f)
                if got != want:
                    raise ArtifactCorruptError(
                        f"artifact file {rel} is corrupt: sha256 {got} "
                        f"does not match the manifest's {want}")
        cfg_d = dict(lm["config"])
        cfg_d["block_pattern"] = tuple(cfg_d.get("block_pattern") or ())
        cfg = LMConfig(**cfg_d)
        # the tree's structure only: no parameters are drawn
        template = jax.eval_shape(lambda k: init_params(cfg, k),
                                  jax.random.PRNGKey(0))
        try:
            params, _, _ = CheckpointStore(path / "weights").restore(
                template, step=0)
        except (ValueError, FileNotFoundError, KeyError) as e:
            raise ArtifactCorruptError(
                f"artifact weights under {path}/weights are corrupt or "
                f"incomplete: {e}") from e
        params = jax.device_put(params)   # once, not on every call
        sess = cls(cfg, params, max_len=int(lm["max_len"]),
                   batch=int(lm["batch"]),
                   seq_buckets=[int(b) for b in lm.get("seq_buckets", [])],
                   model_name=manifest.get("model"))
        for s, c in (lm.get("traffic", {}).get("histogram") or {}).items():
            sess.traffic.add(int(s), int(c))
        return sess


def compile_lm(model: Union[LMConfig, str], *,
               max_len: int, batch: int = 1,
               seq_buckets: Union[None, str, Sequence[int]] = None,
               prompt_hist=None, max_seq_buckets: int = 8,
               seed: int = 0, params=None,
               prewarm: bool = False) -> LMSession:
    """Build an :class:`LMSession` — the LM arm of ``engine.compile``.

    model        an ``LMConfig`` (e.g. ``reduced(ARCHS["qwen2-1.5b"])``)
                 or an assigned-architecture name
    seq_buckets  explicit prefill bucket lengths; ``"auto"`` solves them
                 from ``prompt_hist`` (a ``{len: count}`` mapping or
                 ``SizeHistogram``) via the reflected exact DP; default
                 ``None`` uses the halving ladder
                 ``{max_len, max_len//2, max_len//4}``
    prompt_hist  measured prompt-length histogram for ``"auto"``
    """
    if isinstance(model, str):
        archs = _lm_archs()
        if model not in archs:
            raise ValueError(f"unknown LM architecture {model!r}; "
                             f"pick one of {sorted(archs)}")
        cfg = archs[model]
    else:
        cfg = model
    if seq_buckets == "auto":
        if prompt_hist is None:
            raise ValueError("seq_buckets='auto' needs prompt_hist= a "
                             "recorded prompt-length histogram")
        counts = _coerce_counts(prompt_hist)
        solved = solve_seq_buckets(counts, max_buckets=max_seq_buckets)
        buckets = [b for b in solved if b <= max_len]
    elif seq_buckets is None:
        if prompt_hist is not None:
            raise ValueError("prompt_hist= is only meaningful with "
                             "seq_buckets='auto'")
        buckets = sorted({max_len, max(1, max_len // 2),
                          max(1, max_len // 4)})
    else:
        buckets = [int(b) for b in seq_buckets]
    if params is None:
        params = init_params(cfg, jax.random.PRNGKey(seed))
    sess = LMSession(cfg, params, max_len=max_len, batch=batch,
                     seq_buckets=buckets,
                     model_name=cfg.name if isinstance(model, LMConfig)
                     else model)
    if prompt_hist is not None and seq_buckets == "auto":
        for s, c in _coerce_counts(prompt_hist).items():
            sess.traffic.add(s, c)
    if prewarm:
        sess.prewarm()
    return sess

"""CI smoke for the LM serving path.

Builds a seq-bucketed ``LMSession`` (buckets solved from a synthetic
prompt-length histogram by the traffic DP), saves the v5 artifact, then
loads it back with ``LMSession.load`` and gates on the loaded session:

* load -> generate runs **zero** schedule searches
  (``core.local_search.search_calls()`` does not move across it), and
  every generation is bit-identical to the tokens the built session
  produced before saving;
* ``AsyncServer.submit_stream`` tokens are bit-identical to the
  non-streamed ``generate`` loop (stream == batch semantics), and
  streams execute alone (batch_hist.max_size == 1).

Build and reload share one process: a JAX device belongs to one process
at a time, so a child could not get the chip the parent holds.

Writes BENCH_lm.json with the solved bucket set, load/prewarm wall
times, and decode throughput of the loaded session.

    PYTHONPATH=../src python lm_serving.py --smoke --out ../BENCH_lm.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

import numpy as np


def check_loaded(artifact: Path, prompts, want, gen: int) -> dict:
    """Load the artifact and run both gates on it; returns the timings."""
    import jax.numpy as jnp

    from repro.core.local_search import search_calls
    from repro.engine import AsyncServer, DynamicBatchPolicy, LMSession

    n_searches = search_calls()
    t0 = time.perf_counter()
    sess = LMSession.load(artifact)
    t_load = time.perf_counter() - t0
    t0 = time.perf_counter()
    sess.prewarm()
    t_warm = time.perf_counter() - t0

    # gate 1: load -> generate is zero-search and bit-identical
    t0 = time.perf_counter()
    plain = {k: np.asarray(sess.generate(jnp.asarray(p), gen))
             for k, p in prompts.items()}
    t_gen = time.perf_counter() - t0
    assert search_calls() == n_searches, \
        f"load->generate ran {search_calls() - n_searches} schedule " \
        "searches (want 0)"
    for k in prompts:
        assert plain[k].tobytes() == want[k].tobytes(), \
            f"reload token drift on prompt {k}"

    # gate 2: streamed decode == the non-streamed loop, bit for bit, and
    # each stream executed alone
    srv = AsyncServer(sess, DynamicBatchPolicy(max_batch=4, max_wait_ms=1.0))
    try:
        streams = [(k, srv.submit_stream(jnp.asarray(p), gen))
                   for k, p in prompts.items()]
        for k, s in streams:
            toks = [np.asarray(t) for t in s]
            assert len(toks) == gen, f"stream {k} yielded {len(toks)} steps"
            assert np.stack(toks, axis=1).tobytes() == plain[k].tobytes(), \
                f"streamed tokens drifted from generate on prompt {k}"
    finally:
        srv.close(drain=True)
    assert search_calls() == n_searches, "streaming ran a schedule search"
    assert srv.stats.batch_hist.max_size == 1, \
        "a stream was packed with other requests"
    print(f"loaded session: {len(prompts)} generations zero-search, "
          f"streamed == generate bit-identical "
          f"(seq_buckets={sess.seq_buckets})")
    n_tok = gen * len(prompts) * sess.batch
    return {"t_load_s": round(t_load, 4), "t_prewarm_s": round(t_warm, 4),
            "decode_tok_per_s": round(n_tok / t_gen, 2),
            "n_generations": len(prompts), "zero_search": True,
            "stream_bit_identical": True}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--max-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=5)
    ap.add_argument("--requests", type=int, default=6,
                    help="prompt count (lengths drawn from the synthetic "
                         "histogram the buckets are solved from)")
    ap.add_argument("--max-seq-buckets", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="kept for CI-lane symmetry; the benchmark is "
                         "already smoke-sized")
    ap.add_argument("--out", default="BENCH_lm.json")
    ap.add_argument("--artifact-out", default=None,
                    help="keep the LM artifact here (default: temp dir)")
    args = ap.parse_args()

    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.engine import compile_lm, expected_catchup_tokens

    cfg = reduced(ARCHS[args.arch])
    max_prompt = args.max_len - args.gen + 1
    # synthetic prompt-length demand: short-head + long-tail, the shape
    # the seq-bucket DP earns its keep on
    hist = {max(1, max_prompt // 4): 40, max(2, max_prompt // 2): 25,
            max_prompt: 10}
    t0 = time.perf_counter()
    sess = compile_lm(cfg, max_len=args.max_len, seq_buckets="auto",
                      prompt_hist=hist,
                      max_seq_buckets=args.max_seq_buckets, seed=0)
    t_compile = time.perf_counter() - t0
    catchup = expected_catchup_tokens(hist, sess.seq_buckets)

    rng = np.random.default_rng(0)
    lens = rng.choice(sorted(hist), size=args.requests,
                      p=np.asarray([hist[k] for k in sorted(hist)])
                      / sum(hist.values()))
    prompts = {str(i): rng.integers(0, cfg.vocab,
                                    size=(sess.batch, int(n)))
               .astype(np.int32) for i, n in enumerate(lens)}
    tokens = {k: np.asarray(sess.generate(jnp.asarray(p), args.gen))
              for k, p in prompts.items()}

    out_dir = Path(args.artifact_out) if args.artifact_out else \
        Path(tempfile.mkdtemp(prefix="lm_smoke_")) / "ARTIFACT_lm"
    sess.save(out_dir)
    print(f"saved LM artifact to {out_dir} (arch={args.arch}, "
          f"max_len={args.max_len}, seq_buckets={sess.seq_buckets}, "
          f"expected catch-up {catchup} decode tokens on the histogram)")
    loaded = check_loaded(out_dir, prompts, tokens, args.gen)

    report = {"benchmark": "lm_serving", "arch": args.arch,
              "family": cfg.family, "max_len": args.max_len,
              "gen": args.gen, "seq_buckets": list(sess.seq_buckets),
              "prompt_hist": {str(k): v for k, v in sorted(hist.items())},
              "expected_catchup_tokens": catchup,
              "t_compile_s": round(t_compile, 4), **loaded}
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}: LM artifact round-trip OK "
          f"(zero search, streamed == generate)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()

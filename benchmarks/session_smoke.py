"""CI smoke for the InferenceSession artifact path.

Builds a session with ``tuning="cached"``, saves the versioned artifact,
then loads it back with ``InferenceSession.load`` and runs one predict
from the loaded session, asserting

* the loaded output is bit-identical to the built session's, and
* the load->predict path ran **zero** schedule searches
  (``core.local_search.search_calls()`` does not move across it).

Build and reload share one process: a JAX device belongs to one process
at a time, so a child that reloaded the artifact could not get the chip
the parent holds.  The loaded session shares nothing with the built one
but the files on disk.

The artifact directory is left on disk so CI uploads it alongside the
BENCH_*.json files.

    PYTHONPATH=../src python session_smoke.py --out ../ARTIFACT_session
"""
from __future__ import annotations

import argparse
from pathlib import Path

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet-18")
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--batches", default=None,
                    help="extra batch sizes to specialize+save (comma "
                         "list, e.g. '1,8') — the serving buckets the "
                         "serving_load benchmark packs into")
    ap.add_argument("--db", default=None,
                    help="schedule database to serve cached winners from "
                         "(e.g. BENCH_variants_db.json); omitted = "
                         "roofline-filled cache")
    ap.add_argument("--out", default="ARTIFACT_session")
    args = ap.parse_args()

    import jax.numpy as jnp
    from repro.core.local_search import search_calls
    from repro.engine import InferenceSession
    from repro.engine import compile as compile_session

    if args.db and not Path(args.db).exists():
        # fail loudly: CI passes the smoke variants db so the cached path
        # exercises measured winners — a typo'd/reordered path must not
        # silently degrade this step to an empty cache
        raise SystemExit(f"--db {args.db} does not exist")
    sess = compile_session(args.model,
                           (args.batch, 3, args.image, args.image),
                           tuning="cached", db=args.db)
    for b in sorted(int(s) for s in (args.batches or "").split(",") if s):
        sess.specialize(b)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(args.batch, 3, args.image, args.image)) \
        .astype(np.float32)
    y = np.asarray(sess.predict(jnp.asarray(x)))
    out = Path(args.out)
    sess.save(out)
    print(f"saved artifact to {out} (model={args.model}, "
          f"image={args.image}, batch={args.batch})")

    n_searches = search_calls()
    loaded = InferenceSession.load(out)
    got = np.asarray(loaded.predict(jnp.asarray(x)))
    assert search_calls() == n_searches, \
        f"load->predict ran {search_calls() - n_searches} schedule " \
        "searches (want 0)"
    assert got.shape == y.shape and got.tobytes() == y.tobytes(), \
        f"reload drift: max|delta|={np.abs(got - y).max()}"
    print(f"loaded session: predict bit-identical, zero search "
          f"(batches={loaded.batch_sizes}, frozen={loaded.frozen})")
    print("session artifact round-trip OK")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()

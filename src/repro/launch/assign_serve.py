"""Assignment-serving driver: membership-as-a-service over a synthetic
federation.

Builds a clustered synthetic engine, stands up an
:class:`repro.serving.AssignmentServer`, fires batched assignment queries
at it and prints p50/p99 latency plus sustained QPS; then demonstrates the
epoch swap by submitting churn and draining mid-serve.  (The LM
decode-loop demo lives in ``repro.launch.serve``.)

``python -m repro.launch.assign_serve --clients 512 --queries 256 --batch 32``
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.angles import proximity_matrix
from repro.core.engine import ClusterEngine, EngineConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import REPRESENTATIVE_KINDS, AssignmentServer


def _clustered_signatures(K, n_bases=64, n=64, p=5, seed=0):
    key = jax.random.PRNGKey(seed)
    kb, kc = jax.random.split(key)
    bases = jnp.stack([
        jnp.linalg.qr(jax.random.normal(jax.random.fold_in(kb, i), (n, p)))[0]
        for i in range(n_bases)
    ])
    noise = 0.15 * jax.random.normal(kc, (K, n, p))
    X = bases[jnp.arange(K) % n_bases] + noise
    return jax.vmap(lambda x: jnp.linalg.qr(x)[0])(X)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=512)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--n-bases", type=int, default=64)
    ap.add_argument("--measure", choices=("eq2", "eq3"), default="eq3")
    ap.add_argument(
        "--representative", choices=REPRESENTATIVE_KINDS, default="medoid"
    )
    ap.add_argument("--churn", type=int, default=8,
                    help="joins to submit + drain mid-serve (0 disables)")
    args = ap.parse_args()
    enable_compile_cache()

    K, Q, B = args.clients, args.queries, args.batch
    U_all = _clustered_signatures(K + Q + args.churn, n_bases=args.n_bases)
    U_seen, pool = U_all[:K], U_all[K : K + Q]
    A = np.asarray(
        proximity_matrix(U_seen, args.measure, backend="jnp_blocked")
    )
    beta = float(np.quantile(A[A > 0], 0.05))
    engine = ClusterEngine.from_proximity(
        A, U_seen, EngineConfig(beta=beta, measure=args.measure)
    )
    engine.warm_cache()
    server = AssignmentServer(
        engine, representative=args.representative, batch_max=B
    )
    C = int(server.snapshot.rep_labels.size)
    print(f"engine: K={K} C={C} beta={beta:.2f}deg "
          f"measure={args.measure} representative={args.representative}")

    server.assign(pool[:B])  # warmup: compile the dispatch for this bucket
    lat = []
    assigned = 0
    t_all = time.perf_counter()
    for lo in range(0, Q - B + 1, B):
        t0 = time.perf_counter()
        res = server.assign(pool[lo : lo + B])
        lat.append((time.perf_counter() - t0) * 1e3)
        assigned += int((res.labels >= 0).sum())
    wall = time.perf_counter() - t_all
    lat.sort()
    n = len(lat)
    p50 = lat[n // 2]
    p99 = lat[min(n - 1, int(n * 0.99))]
    total = n * B
    print(f"served {total} queries in {n} batches of {B}: "
          f"p50={p50:.2f}ms p99={p99:.2f}ms per batch "
          f"({p50 / B * 1e3:.0f}us/query p50), {total / wall:.0f} qps; "
          f"{assigned}/{total} assigned within beta")

    if args.churn:
        snap = server.snapshot
        for i in range(args.churn):
            server.submit_join(U_all[K + Q + i])
        report = server.drain()
        res_old = server.assign(pool[:B], snapshot=snap)
        res_new = server.assign(pool[:B])
        print(f"drained {report.joins} joins -> epoch {report.epoch} "
              f"(C={server.snapshot.rep_labels.size}); held pre-drain "
              f"snapshot still answers epoch {res_old.epoch}, "
              f"current answers epoch {res_new.epoch}")


if __name__ == "__main__":
    main()

"""Closed loop of churn drains through ``AssignmentServer``.

Set-up bootstraps the resident population (not timed).  Each drain is
``leaves`` ``submit_leave`` calls and ``joins`` ``submit_join`` calls, then
``drain()``.  Of the leaves, ``far_leaves`` are far clients and the rest
near, each drawn uniformly from the live clients of its kind; of the joins,
``far_joins`` are far.  So K and the number of clusters stay fixed: the
server stacks one representative per cluster, and a stack of a new height
would compile inside the window.  The events are drawn from the seed before the
drain's clock starts.  A drain's latency runs from its first submit until
``drain()`` has returned and the new epoch's arrays are ready.
``drain_p50_ms`` and ``drain_p80_ms`` are the median and the 80th
percentile over the window's drains.

The check reads what clients are served: the epoch's snapshot (its engine
fork, representative stack and labels), never the write-side engine.
"""
from __future__ import annotations

import time

import numpy as np

import clusters
import reference


class Churn:
    def __init__(self, run):
        self.run = run
        self.cfg = run.config
        self.tr = run.traffic

    def setup(self) -> None:
        from repro.core.engine import ClusterEngine
        from repro.serving import AssignmentServer

        cfg, tr = self.cfg, self.tr
        self.pop, U, self.is_far = clusters.resident(cfg, self.run.seed)
        K = U.shape[0]
        # stable ids are handed out in join order: id i is row i of sigs
        self.sig_chunks = [U]
        self.live = np.arange(K)
        self.next_join = K
        self.rng = np.random.default_rng([self.run.seed, 2])
        self.server = AssignmentServer(ClusterEngine.from_signatures(U, clusters.engine_config(cfg)))
        for _ in range(int(tr["warm_drains"])):
            self._drain()

    @property
    def sigs(self) -> np.ndarray:
        if len(self.sig_chunks) > 1:
            self.sig_chunks = [np.concatenate(self.sig_chunks)]
        return self.sig_chunks[0]

    def _events(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The next drain's leaving ids, joining ids and joining signatures."""
        tr, live, pop = self.tr, self.live, self.pop
        nl, nfl = int(tr["leaves"]), int(tr["far_leaves"])
        nj, nfj = int(tr["joins"]), int(tr["far_joins"])
        far_live = self.is_far[live]
        leave = np.concatenate([
            self.rng.choice(live[~far_live], nl - nfl, replace=False),
            self.rng.choice(live[far_live], nfl, replace=False),
        ])
        self.rng.shuffle(leave)
        join = np.arange(self.next_join, self.next_join + nj)
        U_join = np.concatenate([pop.near(pop.rng.integers(0, pop.n_styles, nj - nfj)), pop.far(nfj)])
        self.sig_chunks.append(U_join)
        self.is_far = np.concatenate([self.is_far, np.arange(nj) >= nj - nfj])
        return leave, join, U_join

    def _drain(self) -> float:
        leave, join, U_join = self._events()
        srv = self.server
        t0 = time.perf_counter()
        with self.run.spans.span("drain"):
            for cid in leave:
                srv.submit_leave(int(cid))
            for u in U_join:
                srv.submit_join(u)
            srv.drain()
            srv.snapshot.rep_stack.block_until_ready()
            srv._write.U.block_until_ready()
        dt = time.perf_counter() - t0
        self.live = np.concatenate([np.setdiff1d(self.live, leave), join])
        self.next_join = int(join[-1]) + 1
        return dt

    def window(self, seconds: float) -> None:
        run = self.run
        keep = int(self.tr["check_drains"])
        pick = np.random.default_rng([run.seed, 3])
        times, self.sample = [], []
        self.epochs = [self.server.snapshot.epoch]
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            times.append(self._drain())
            snap = self.server.snapshot
            self.epochs.append(snap.epoch)
            # seeded reservoir sample of the served snapshots, with the
            # driver's own roster at that drain
            i = len(times) - 1
            if i < keep:
                self.sample.append((snap, self.live.copy()))
            else:
                j = int(pick.integers(0, i + 1))
                if j < keep:
                    self.sample[j] = (snap, self.live.copy())
        self.final = (self.server.snapshot, self.live.copy())
        run.steps = run.attempted = len(times)
        run.step_s = times
        run.e2e["drain_p50_ms"] = 1e3 * float(np.median(times))
        run.e2e["drain_p80_ms"] = 1e3 * float(np.percentile(times, 80))

    def _rep_mismatch(self, snap, ids: np.ndarray, A_ref: np.ndarray, labels_ref: np.ndarray) -> int:
        """Clients served wrongly by the snapshot's representatives, counted
        by cluster: a served cluster with no representative or a
        representative with no cluster; a representative that is not, bit
        for bit, the uploaded signature of a member of the cluster it serves;
        a reference cluster whose float64 medoid (the member with the least
        summed distance to the others) is not the one served.  The medoid is
        judged only where the reference's two least sums lie more than
        ``2 |P| max_dev_deg`` apart: closer, a store within its limit may
        rightly pick either."""
        stable = np.asarray(snap.engine.labels)
        rep_labels = np.asarray(snap.rep_labels)
        bad = int(np.setxor1d(rep_labels, stable).size)
        if bad or snap.rep_stack is None:
            return bad + int(snap.rep_stack is None)
        reps = np.asarray(snap.rep_stack)
        sigs = self.sigs[ids]
        row_of = {sig.tobytes(): r for r, sig in enumerate(sigs)}
        for label, rep in zip(rep_labels, reps):
            r = row_of.get(rep.tobytes())
            bad += int(r is None or stable[r] != label)
        if bad:
            return bad
        room = 2 * float(self.cfg["limits"]["max_dev_deg"])
        for c in np.unique(labels_ref):
            pos = np.flatnonzero(labels_ref == c)
            sums = A_ref[np.ix_(pos, pos)].sum(axis=1)
            first, second = np.argsort(sums, kind="stable")[:2] if pos.size > 1 else (0, None)
            if second is not None and sums[second] - sums[first] <= room * pos.size:
                continue
            m = pos[first]
            served = reps[int(np.searchsorted(rep_labels, stable[m]))]
            bad += int(not np.array_equal(served, sigs[m]))
        return bad

    def check(self) -> list:
        cfg, limits = self.cfg, self.cfg["limits"]
        beta, method = float(cfg["beta"]), cfg["linkage"]
        checked = [self.final] + self.sample
        rosters = [np.asarray(snap.engine.ids) for snap, _ in checked]
        # one float64 proximity over every client of the rosters checked
        union = np.unique(np.concatenate(rosters))
        A_union = reference.proximity_f64(self.sigs[union], cfg["measure"])
        roster = label = rep = 0
        refs = []
        for (snap, live), ids in zip(checked, rosters):
            rows = np.searchsorted(union, ids)
            A_ref = A_union[np.ix_(rows, rows)]
            labels_ref = reference.hc_labels(A_ref, beta, method)
            refs.append((A_ref, labels_ref))
            roster += int(np.setxor1d(ids, live).size)
            label = max(label, reference.label_mismatch(snap.engine.canonical_labels, labels_ref))
            rep += self._rep_mismatch(snap, ids, A_ref, labels_ref)
        A_ref, labels_ref = refs[0]
        if self.run.control:
            # the reference one precision step lower, in the served state's place
            A = reference.proximity_control(self.sigs[rosters[0]], cfg["measure"])
            label = max(label, reference.label_mismatch(reference.hc_labels(A, beta, method), labels_ref))
        else:
            A = self.final[0].engine.dense(np.float64)
        epochs = np.asarray(self.epochs)
        return [
            ("max_dev_deg", float(np.abs(A - A_ref).max()), limits["max_dev_deg"]),
            ("label_mismatch", label, limits["label_mismatch"]),
            ("roster_mismatch", roster, limits["roster_mismatch"]),
            ("rep_mismatch", rep, limits["rep_mismatch"]),
            ("epoch_skips", int((np.diff(epochs) != 1).sum()), limits["epoch_skips"]),
        ]


def make(run):
    return Churn(run)

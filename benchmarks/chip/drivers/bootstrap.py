"""Closed loop of one-shot bootstraps: ``ClusterEngine.from_signatures``.

Each step hands the engine the population's signatures as host arrays, in a
fresh seeded row order drawn before the step's clock starts, and ends with
the HC labels on the host.  ``bootstrap_s`` is the mean step time over the
window.  A seeded reservoir keeps a few finished engines for the check.
"""
from __future__ import annotations

import time

import numpy as np

import clusters
import reference


class Bootstrap:
    def __init__(self, run):
        self.run = run
        self.cfg = run.config

    def setup(self) -> None:
        from repro.core.engine import ClusterEngine

        self.ecfg = clusters.engine_config(self.cfg)
        _, self.U, _ = clusters.resident(self.cfg, self.run.seed)
        self.rng = np.random.default_rng([self.run.seed, 1])
        if self.run.control:
            self.step = self._control_step
        else:
            self.step = lambda U: ClusterEngine.from_signatures(U, self.ecfg)
        self.step(self.U[self.rng.permutation(self.U.shape[0])]).labels

    def _control_step(self, U):
        A = reference.proximity_control(U, self.cfg["measure"])
        return _Stand(A, reference.hc_labels(A, float(self.cfg["beta"]), self.cfg["linkage"]))

    def window(self, seconds: float) -> None:
        run, K = self.run, self.U.shape[0]
        keep = int(run.traffic["check_sample"])
        self.sample: list[tuple[np.ndarray, object]] = []
        times = []
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            perm = self.rng.permutation(K)
            Up = self.U[perm]
            t0 = time.perf_counter()
            with run.spans.span("bootstrap"):
                eng = self.step(Up)
                eng.labels  # the step ends with the labels on the host
            times.append(time.perf_counter() - t0)
            # seeded reservoir sample of the finished bootstraps
            i = len(times) - 1
            if i < keep:
                self.sample.append((perm, eng))
            else:
                j = int(self.rng.integers(0, i + 1))
                if j < keep:
                    self.sample[j] = (perm, eng)
            del eng
        run.steps = run.attempted = len(times)
        run.step_s = times
        run.e2e["bootstrap_s"] = float(np.sum(times) / len(times))

    def check(self) -> list:
        cfg = self.cfg
        A_ref = reference.proximity_f64(self.U, cfg["measure"])
        labels_ref = reference.hc_labels(A_ref, float(cfg["beta"]), cfg["linkage"])
        dev, mism = 0.0, 0
        for perm, eng in self.sample:
            A = eng.dense(np.float64)
            dev = max(dev, float(np.abs(A - A_ref[np.ix_(perm, perm)]).max()))
            mism = max(mism, reference.label_mismatch(eng.labels, labels_ref[perm]))
        return [
            ("max_dev_deg", dev, cfg["limits"]["max_dev_deg"]),
            ("label_mismatch", mism, cfg["limits"]["label_mismatch"]),
        ]


class _Stand:
    """The control's output in the shape the check reads."""

    def __init__(self, A, labels):
        self._A, self.labels = A, labels

    def dense(self, dtype):
        return self._A.astype(dtype)


def make(run):
    return Bootstrap(run)

"""Device heterogeneity & dynamic-state models (paper Sec. 6.1).

Two profiles:
  * ``paper_edge`` — phone-class devices: CPU freq ~ U(1, 2) GHz resampled
    every round (dynamic state) MODULATED by each device's persistent
    capability (a slow phone is slow every round, not just unlucky once),
    bandwidth ~ U(1, 5) Mbps, p ~ U(0.1, 1) W.
  * ``tpu_pod`` — datacenter profile for the LM architectures: per-replica
    step time with lognormal jitter (stragglers), inter-cluster links at
    backbone bandwidth.  Same (mu, nu, alpha, p) interface: the controller
    is agnostic to where the numbers come from.

Population mode (DESIGN.md §Cohort contract): with ``population`` set the
model describes N >> R logical clients, each with a PERSISTENT identity —
capability and availability propensity drawn once from the population
distribution at construction — while the per-round dynamic state (freq
jitter, bandwidth) is resampled every round, seeded by (seed, round) so
any cohort's reports are reproducible without materializing the rest of
the population's rounds.  ``sample_round(round, ids=...)`` returns the
reports for exactly the sampled cohort; ``sample_cohort`` draws a
mesh-sized cohort from the clients whose availability churn left them
reachable this round.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro.core.controller import DeviceReports


@dataclass
class HeterogeneityModel:
    num_devices: int  # cohort (mesh) size R
    profile: str = "paper_edge"
    seed: int = 0
    model_bits: float = 269_722 * 32  # full-model upload size (bits)
    flops_per_iter: float = 123.9e6 * 50 * 3  # fwd+bwd, batch 50
    base_step_time: float = 1.0  # tpu_pod: mean step seconds
    backhaul_mbps: float = 50.0
    # --- population mode: N logical clients behind an R-slot mesh ---
    population: int = 0  # 0 -> population == num_devices (no sampling)
    avail_lo: float = 0.6   # per-client availability propensity range:
    avail_hi: float = 0.95  # client i is reachable w.p. avail_p[i] / round

    def __post_init__(self):
        if self.population and self.population < self.num_devices:
            raise ValueError(
                f"population {self.population} smaller than the cohort "
                f"size {self.num_devices}")
        N = self.population_size
        rng = np.random.default_rng(self.seed)
        # static part of heterogeneity: relative device capability —
        # drawn FIRST so legacy (population=0) capability streams are
        # unchanged; persistent per client for the whole campaign.
        self.capability = rng.uniform(0.5, 1.0, N)
        self.avail_p = rng.uniform(self.avail_lo, self.avail_hi, N)

    @property
    def population_size(self) -> int:
        return self.population or self.num_devices

    # ------------------------------------------------------------------
    def sample_round(self, round_idx: int, ids=None) -> DeviceReports:
        """Per-round device reports.  ``ids`` selects a cohort of logical
        clients (default: clients 0..R-1, which with population=0 is the
        whole legacy device set — bit-identical to the pre-cohort path).
        Dynamic state is drawn population-wide from the (seed, round)
        stream and indexed, so a client's round-r report is the same no
        matter which cohort it lands in."""
        with jax.profiler.TraceAnnotation("hcef.reports"):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, round_idx]))
            N = self.population_size
            if self.profile == "paper_edge":
                # dynamic U(1, 2) GHz throttle on top of the persistent
                # capability: a cap-0.5 phone spans [0.5, 1] GHz effective,
                # a cap-1.0 phone [1, 2] GHz — persistent speed identity
                # (the paper's U(1, 2)-only model made every device
                # exchangeable across rounds).
                freq = rng.uniform(1.0, 2.0, N) * self.capability
                mu = 150.0 / freq
                alpha = 1.5 * freq ** 2
                bw = rng.uniform(1.0, 5.0, N) * 1e6  # bit/s
                nu = self.model_bits / bw
                p = rng.uniform(0.1, 1.0, N)
            elif self.profile == "tpu_pod":
                jitter = rng.lognormal(0.0, 0.25, N)
                mu = self.base_step_time * jitter / self.capability
                alpha = 200.0 * mu  # ~200 W replica draw
                bw = rng.uniform(0.5, 1.0, N) * 100e9  # 100 Gb/s class links
                nu = self.model_bits / bw
                p = np.full(N, 300.0)
            else:
                raise ValueError(self.profile)
            ids = (np.arange(self.num_devices) if ids is None
                   else np.asarray(ids, np.int64))
            if ids.size and (ids.min() < 0 or ids.max() >= N):
                raise ValueError(f"cohort ids out of range(population={N})")
            # sigma2/G2 placeholders; overwritten by measured values in
            # training
            return DeviceReports(sigma2=np.ones(ids.size),
                                 G2=np.ones(ids.size), mu=mu[ids],
                                 alpha=alpha[ids], nu=nu[ids], p=p[ids])

    # ------------------------------------------------------------------
    def available(self, round_idx: int) -> np.ndarray:
        """(N,) availability churn mask: client i is reachable this round
        w.p. its persistent propensity avail_p[i] (seeded per round —
        replayable, independent of the report stream)."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 7919, round_idx]))
        return rng.random(self.population_size) < self.avail_p

    def sample_cohort(self, round_idx: int, cohort: int,
                      seed: int = 0) -> np.ndarray:
        """Draw a mesh-sized cohort uniformly from this round's AVAILABLE
        clients (top up from the full population in the degenerate case
        where churn leaves fewer than ``cohort`` reachable — the mesh has
        a fixed slot count).  Slot order is the sampled order, which is
        also the cohort's cluster assignment (slot r -> cluster r//Dev).
        Deterministic in (seed, round): replays and restores resample the
        identical cohort trace."""
        if cohort > self.population_size:
            raise ValueError(f"cohort {cohort} exceeds population "
                             f"{self.population_size}")
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 104_729, round_idx]))
        avail = np.flatnonzero(self.available(round_idx))
        if avail.size >= cohort:
            return rng.choice(avail, cohort, replace=False).astype(np.int64)
        rest = np.setdiff1d(np.arange(self.population_size), avail)
        fill = rng.choice(rest, cohort - avail.size, replace=False)
        ids = np.concatenate([avail, fill]).astype(np.int64)
        return rng.permutation(ids)

    def backhaul_time(self) -> float:
        return self.model_bits / (self.backhaul_mbps * 1e6)

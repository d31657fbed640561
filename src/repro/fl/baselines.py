"""Round controllers: HCEF + the paper's benchmark schemes (Sec. 6.1),
plus pluggable LOCAL objectives (FedProx) for the cohort regime."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import BudgetState, DeviceReports, solve_p2


class Controller:
    name = "base"

    def __init__(self, tau: int, theta_min=0.05, rho_min=0.1):
        self.tau = tau
        self.theta_min = theta_min
        self.rho_min = rho_min
        # solver honesty flags from the last controls() call (e.g.
        # p21_time_infeasible — the per-round time allowance could not be
        # met even at theta_min; see core.controller.solve_p2).
        self.diag: dict = {}

    def controls(self, reports: DeviceReports, budget: BudgetState):
        """(rho, theta) for this round; the solve is traced as the host
        span ``hcef.controller``."""
        with jax.profiler.TraceAnnotation("hcef.controller"):
            return self.solve(reports, budget)

    def solve(self, reports: DeviceReports, budget: BudgetState):
        raise NotImplementedError


class HCEF(Controller):
    """Joint adaptive rho & theta (Algorithm 3)."""
    name = "hcef"

    def solve(self, reports, budget):
        self.diag = {}
        return solve_p2(reports, budget, self.tau, self.theta_min,
                        self.rho_min, diagnostics=self.diag)


class CEF(Controller):
    """CE-FedAvg: heterogeneity-oblivious (rho = theta = 1)."""
    name = "cef"

    def solve(self, reports, budget):
        N = len(reports.mu)
        return np.ones(N), np.ones(N)


class CEF_F(Controller):
    """Adaptive local update frequency only (theta = 1)."""
    name = "cef_f"

    def solve(self, reports, budget):
        self.diag = {}
        return solve_p2(reports, budget, self.tau, self.theta_min,
                        self.rho_min, fix_theta=1.0,
                        diagnostics=self.diag)


class CEF_C(Controller):
    """Adaptive compression only (rho = 1)."""
    name = "cef_c"

    def solve(self, reports, budget):
        self.diag = {}
        return solve_p2(reports, budget, self.tau, self.theta_min,
                        self.rho_min, fix_rho=1.0,
                        diagnostics=self.diag)


class MLL_SGD(Controller):
    """rho_n proportional to device speed relative to the fastest device
    (Castiglia et al.); theta = 1.  (The paper's prose normalizes by the sum,
    which would send rho -> 1/N; we use the standard relative-to-fastest form
    so the baseline is competitive, as in the original MLL-SGD.)"""
    name = "mll_sgd"

    def solve(self, reports, budget):
        inv = 1.0 / np.maximum(reports.mu, 1e-12)
        rho = inv / inv.max()
        return np.clip(rho, self.rho_min, 1.0), np.ones(len(rho))


CONTROLLERS = {c.name: c for c in (HCEF, CEF, CEF_F, CEF_C, MLL_SGD)}


def make_controller(name: str, tau: int, **kw) -> Controller:
    return CONTROLLERS[name](tau, **kw)


# ---------------------------------------------------------------------------
# Pluggable local objectives.
#
# Cohort sampling makes client drift real: a client that participates once
# every ~population/cohort rounds takes tau local steps from a model that
# moved a long way since its last look, and its non-IID shard pulls it
# further.  FedProx (Li et al., MLSys 2020) damps the drift with a proximal
# term anchored at the ROUND-START model w0:
#
#     f_prox(w; b) = f(w; b) + (prox_mu / 2) * ||w - w0||^2
#
# The local objective is threaded through the tau-step scan as
# ``objective(params, batch, anchor)`` so the anchor rides the carry; plain
# SGD ignores it via a closure that does not touch x0 — the jaxpr is
# IDENTICAL to the pre-objective path, keeping "sgd" bitwise-stable.


def make_local_objective(name: str, loss_fn, *, prox_mu: float = 0.01):
    """Wrap a per-device ``loss_fn(params, batch)`` into a local objective
    ``objective(params, batch, anchor)`` used inside the tau-step scan.

    ``sgd``:     the loss unchanged (anchor ignored — identical jaxpr).
    ``fedprox``: loss + (prox_mu/2) ||params - anchor||^2 with the anchor
                 frozen at the round-start model (lax.stop_gradient is
                 unnecessary: the anchor enters the scan as a constant
                 carry and is never differentiated against).
    """
    if name == "sgd":
        return lambda params, batch, anchor: loss_fn(params, batch)
    if name == "fedprox":
        mu = float(prox_mu)

        def objective(params, batch, anchor):
            loss = loss_fn(params, batch)
            sq = jax.tree.map(
                lambda w, a: jnp.sum(jnp.square(w - a.astype(w.dtype))),
                params, anchor)
            prox = jax.tree.reduce(jnp.add, sq)
            return loss + (mu / 2.0) * prox.astype(loss.dtype)

        return objective
    raise ValueError(f"unknown local objective {name!r} "
                     f"(expected 'sgd' or 'fedprox')")


LOCAL_OBJECTIVES = ("sgd", "fedprox")

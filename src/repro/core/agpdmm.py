"""AGPDMM (Algorithm 2, Zhang et al. 2021): accelerated GPDMM.

Differences from GPDMM (Alg. 1):
  * client init x_i^{r,0} = x_s^r (the fresher global estimate), so no
    per-client primal carry is stored;
  * the dual update uses the LAST iterate x_i^{r,K} (eq. 24), not the average.

Those two differences are all there is: AGPDMM runs through GPDMM's round
bodies (pytree, masked arena, cohort and popstore) with ``accel=True``
bound (``core.gpdmm``).

The paper states AGPDMM transmits two variables server->client (x_s and
lam_{s|i}).  In the SPMD mapping the downlink lam_{s|i}^{r+1} =
rho (x_i^{r,K} - x_s^{r+1}) - lam_{i|s}^{r+1} is recomputed client-locally
from x_s^{r+1} and client-resident quantities, so the realised collective
traffic equals GPDMM's (one all-reduce per round).  This implementation
observation is recorded in EXPERIMENTS.md SSPerf.

When K == 1 and rho = 1/eta, the round reduces exactly to vanilla gradient
descent with stepsize eta (paper eq. (27)); ``tests/test_core.py`` asserts
this identity numerically.
"""
from __future__ import annotations

from repro.configs.base import FederatedConfig
from repro.core import gpdmm
from repro.core.api import FedOpt


def make(cfg: FederatedConfig) -> FedOpt:
    return gpdmm.make(cfg, accel=True)._replace(name="agpdmm")

"""Plain pieces of Ouro's exit objective for `tests/test_ouro.py`, each
written as its equation reads, in numpy float64, sharing nothing with
`mxtpu.parallel.transformer` or with the benchmark's reference
(`benchmark/onchip/reference/ouro_2_6b.py`, which the whole-stack tests
use): the exit distribution as a product of probabilities, and the
stage-1 objective from the per-pass losses.
"""
import numpy as np


def exit_distribution(s):
    """p [U, N] from the gate's logits s [U - 1, N] at every pass but the
    last: lambda = 1 / (1 + exp(-s)); p_t = lambda_t prod_{j<t} (1 -
    lambda_j) for t < U; p_U = prod_{j<U} (1 - lambda_j)."""
    s = np.asarray(s, np.float64)
    lam = 1.0 / (1.0 + np.exp(-s))
    # 1 - lambda as sigmoid(-s): a difference from 1 would keep three
    # digits of it where lambda is within 1e-13 of 1
    stays = 1.0 / (1.0 + np.exp(s))
    p, stay = [], np.ones(s.shape[1])
    for t in range(s.shape[0]):
        p.append(lam[t] * stay)
        stay = stay * stays[t]
    return np.stack(p + [stay])


def objective(nll, s, beta):
    """Per token, sum_t p_t nll_t + beta sum_t p_t log p_t, from the
    per-pass losses nll [U, N] and the gate's logits s [U - 1, N]."""
    p = exit_distribution(s)
    return (p * np.asarray(nll, np.float64)).sum(0) \
        + beta * (p * np.log(p)).sum(0)

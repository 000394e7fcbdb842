"""The benchmark's own copy of the newcomer arm's data (§4.5).

A copy, not an import, of the program's
``repro.core.simulator.extend_with_flash``: a fourth column of judged
rewards and realised costs for Gemini-2.5-Flash, appended to a split of
``data.py``. The profile (quality, $/1k-token rate, mean request
tokens) comes from the configuration file, whose ``assumed`` names the
program's ``good_cheap`` profile; the column is drawn from
``default_rng(data_seed + 17)``, so it is part of the deployment and
run seeds never move it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from perfbench import data

# Spread of the newcomer's per-request token counts (log-normal sigma):
# a coefficient of variation near 1.5 (the paper's Appendix B).
_TOKEN_SIGMA = 1.1


def with_newcomer(env: data.Environment, quality: float, price_per_1k: float,
                  mean_tokens: float, data_seed: int) -> data.Environment:
    """``env`` with one more arm: rewards around ``quality`` with a mild
    right tail, costs log-normal around ``price_per_1k`` x
    ``mean_tokens`` / 1e3 $/request."""
    rng = np.random.default_rng(data_seed + 17)
    n = env.n
    r = quality - 0.03 * rng.standard_normal((n,)) ** 2
    r = np.clip(r + data._REWARD_NOISE * rng.standard_normal((n,)), 0.0, 1.0)
    z = rng.standard_normal((n,))
    s = _TOKEN_SIGMA
    tokens = np.exp(np.log(mean_tokens) - 0.5 * s * s + s * z)
    c = price_per_1k * tokens / 1e3
    return dataclasses.replace(
        env,
        rewards=np.concatenate([env.rewards, r[:, None]],
                               axis=1).astype(np.float32),
        costs=np.concatenate([env.costs, c[:, None]],
                             axis=1).astype(np.float32),
        prices_per_1k=np.append(env.prices_per_1k,
                                price_per_1k).astype(np.float32),
        prices_per_req=np.append(env.prices_per_req,
                                 price_per_1k * mean_tokens / 1e3
                                 ).astype(np.float32))


def for_config(config):
    """(train, test) of a configuration whose arms past the data's three
    are newcomers: the train split of the first three (the warm priors
    are fit on it; a newcomer starts cold), and the test split with each
    newcomer's column appended."""
    base = len(data.MODELS)
    p1k = tuple(config["prices_per_1k"])
    tok = tuple(config["mean_request_tokens"])
    seed = int(config["data_seed"])
    train, _, test = data.splits(seed, p1k[:base], tok[:base])
    for k, q in enumerate(config["newcomer_quality"], start=base):
        test = with_newcomer(test, q, p1k[k], tok[k], seed)
    return train, test

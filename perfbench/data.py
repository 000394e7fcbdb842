"""The benchmark's own copy of the paper's evaluation data (§4.1).

A copy, not an import, of the program's synthetic environment
(``repro.core.simulator.make_benchmark`` with its PCA whitener) and of
the tenant-mix helper (``repro.data.synthetic``): the yardstick must not
move when a later change edits the program. The whitener is fitted with
numpy in float64 on the host, so the stream is the same on every
machine and costs no device program.

11,983 prompts from nine task families, split train/val/test =
8,374 / 1,785 / 1,824; three arms (Llama-3.1-8B, Mistral-Large,
Gemini-2.5-Pro) over a ~530x per-request price range; contexts are a
384-d embedding projected to 25 whitened PCA components plus a bias
(d = 26).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np

FAMILIES = (
    "mmlu", "gsm8k", "hellaswag", "bbh", "arc_challenge",
    "openbookqa", "winogrande", "truthfulqa", "mbpp",
)
MODELS = ("llama-3.1-8b", "mistral-large", "gemini-2.5-pro")

# Per-(family, model) mean judge quality; columns llama, mistral, gemini.
QUALITY = np.array(
    [
        [0.8138, 0.9851, 0.9452],
        [0.6908, 0.8401, 0.9632],
        [0.8688, 0.9801, 0.9252],
        [0.7188, 0.8501, 0.9582],
        [0.8188, 0.9851, 0.9452],
        [0.8338, 0.9801, 0.9402],
        [0.8788, 0.9751, 0.9202],
        [0.7688, 0.9701, 0.9152],
        [0.7288, 0.8601, 0.9632],
    ],
    dtype=np.float64,
)
SPLITS = (("train", 8374), ("val", 1785), ("test", 1824))

RAW_DIM = 384
PCA_DIM = 25
_REWARD_NOISE = 0.055
_PROMPT_SPREAD = 0.045
_WEAK_SENSITIVITY = np.array([1.6, 0.9, 0.8])


@dataclasses.dataclass(frozen=True)
class Environment:
    """One split: whitened contexts and the judged (reward, cost) of
    every arm on every prompt. Duck-types the program's environment
    (``k``, ``n``, ``contexts``, ``rewards``, ``costs``, prices)."""

    contexts: np.ndarray        # (N, d) f32
    rewards: np.ndarray         # (N, K) f32 judge scores in [0, 1]
    costs: np.ndarray           # (N, K) f32 realised $/request
    prices_per_req: np.ndarray  # (K,) f32 blended mean $/request
    prices_per_1k: np.ndarray   # (K,) f32 blended $/1k-token rate

    @property
    def n(self) -> int:
        return self.contexts.shape[0]

    @property
    def k(self) -> int:
        return self.rewards.shape[1]

    def subset(self, idx: np.ndarray) -> "Environment":
        return dataclasses.replace(
            self, contexts=self.contexts[idx], rewards=self.rewards[idx],
            costs=self.costs[idx])


def _whiten(raw_train: np.ndarray):
    """PCA(25) + whitening fitted on the train split, as a function of
    raw (N, 384) embeddings -> (N, 26) with the bias appended."""
    x = raw_train.astype(np.float64)
    mean = x.mean(axis=0)
    _, s, vt = np.linalg.svd(x - mean, full_matrices=False)
    comps = vt[:PCA_DIM]
    scale = 1.0 / np.sqrt(s[:PCA_DIM] ** 2 / max(x.shape[0] - 1, 1) + 1e-6)

    def apply(raw):
        z = (raw.astype(np.float64) - mean) @ comps.T * scale
        return np.concatenate([z, np.ones((z.shape[0], 1))], axis=1)

    return apply


def for_config(config) -> Tuple[Environment, Environment, Environment]:
    """(train, val, test) of a configuration file's portfolio."""
    return splits(int(config["data_seed"]), tuple(config["prices_per_1k"]),
                  tuple(config["mean_request_tokens"]))


@functools.lru_cache(maxsize=1)
def splits(seed: int, prices_per_1k: Tuple[float, ...],
           mean_tokens: Tuple[float, ...]
           ) -> Tuple[Environment, Environment, Environment]:
    """(train, val, test), generated from one fixed data seed: the
    dataset is part of the deployment, and run seeds only reorder it.
    ``prices_per_1k`` and ``mean_tokens`` give each arm's blended rate
    and mean request length."""
    rng = np.random.default_rng(seed)
    p1k = np.asarray(prices_per_1k, np.float64)
    mean_tok = np.asarray(mean_tokens, np.float64)
    prices_per_req = p1k * mean_tok / 1e3
    centroids = rng.standard_normal((len(FAMILIES), RAW_DIM))
    raws, fams = {}, {}
    for name, n in SPLITS:
        fam = rng.integers(0, len(FAMILIES), size=n)
        raws[name] = (centroids[fam] + 0.55 * rng.standard_normal(
            (n, RAW_DIM))).astype(np.float32)
        fams[name] = fam
    whiten = _whiten(raws["train"])
    out = []
    for name, n in SPLITS:
        difficulty = rng.standard_normal((n, 1)) * _PROMPT_SPREAD
        r = QUALITY[fams[name]] - difficulty * _WEAK_SENSITIVITY[None, :]
        r = np.clip(r + _REWARD_NOISE * rng.standard_normal((n, 3)), 0, 1)
        shared = rng.standard_normal((n, 1))
        z = 0.72 * shared + 0.69 * rng.standard_normal((n, 3))
        s = 0.75
        tokens = np.exp(np.log(mean_tok)[None, :] - 0.5 * s * s + s * z)
        out.append(Environment(
            contexts=whiten(raws[name]).astype(np.float32),
            rewards=r.astype(np.float32),
            costs=(p1k[None, :] * tokens / 1e3).astype(np.float32),
            prices_per_req=prices_per_req.astype(np.float32),
            prices_per_1k=p1k.astype(np.float32),
        ))
    return tuple(out)


def normalized_weights(weights, T: int) -> np.ndarray:
    """A (T,) mix normalised to sum 1 (None = uniform)."""
    w = (np.ones(T, np.float64) if weights is None
         else np.asarray(weights, np.float64))
    if w.shape != (T,):
        raise ValueError(f"weights must be ({T},); got shape {w.shape}")
    if np.any(w < 0.0) or not w.sum() > 0.0:
        raise ValueError(f"weights must be >= 0 with a positive sum: {w}")
    return w / w.sum()

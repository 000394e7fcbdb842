"""PortfolioServer: ParetoBandit routing wired into real model serving.

This is the framework's integration point for the paper: a portfolio of
*actually served* JAX models (any architecture from repro.configs), a
feature pipeline (hash-encoder + PCA), Algorithm 1 arm selection, greedy
decode on the chosen model, and closed-loop bandit/pacer updates from the
observed (reward, cost).

Rewards come from a pluggable judge. Offline we ship ``SimulatedJudge``
(per-(family, tier) quality + noise — the stand-in for DeepSeek-R1);
in production the same interface is an async LLM-judge callback, which is
why the router caches context vectors at route time (§3.1/§3.6).

``serve_batch`` is the gateway-QPS data plane (DESIGN.md §2/§13): the
block is routed through ``RouterGateway.route_block`` — one
``select_batch`` call against the live double-buffered state, with the
snapshot version recorded per request — generation is grouped by chosen
arm, and the block's feedback is enqueued to the learner plane and
applied by an immediate ``learn_tick`` (publish cadence 1, which makes
the wrapper bit-identical to the old synchronous fold). ``serve`` is
its B = 1 case. Deployments that want the decoupled cadence drive
``self.gateway`` (submit/poll/learn_tick) directly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import registry as registry_lib
from repro.core.costs import ArmPricing
from repro.core.features import PCAWhitener, hash_encode, hash_encode_batch
from repro.core.types import (
    HYPER_FIELDS, HyperParams, RouterConfig, RouterState, init_state,
    with_hyperparams,
)
from repro.models import decode_step, init_model, prefill_forward
from repro.models.config import ModelConfig
from repro.serving.gateway import RouterGateway
from repro.serving.sampler import sample_token
from repro.serving.tokenizer import HashTokenizer


@dataclasses.dataclass
class ServedModel:
    """One portfolio arm: a runnable model + its pricing."""

    name: str
    cfg: ModelConfig
    params: Dict
    pricing: ArmPricing
    tier: str = "mid"  # budget | mid | frontier (judge quality profile)

    @classmethod
    def init(cls, cfg: ModelConfig, pricing: ArmPricing, tier: str,
             seed: int = 0) -> "ServedModel":
        params = init_model(jax.random.PRNGKey(seed), cfg)
        return cls(name=cfg.name, cfg=cfg, params=params, pricing=pricing,
                   tier=tier)

    PROMPT_BUCKET = 32  # pad prompts to a fixed bucket: one compile

    def prompt_window(self, tokens: np.ndarray) -> np.ndarray:
        """The (S,) token window ``generate`` prefills: the prompt
        left-padded with BOS (so the causal suffix is the real prompt) to
        a PROMPT_BUCKET multiple, at most four buckets long."""
        pad = (-len(tokens)) % self.PROMPT_BUCKET or (
            self.PROMPT_BUCKET if len(tokens) == 0 else 0)
        return np.concatenate([np.ones(pad, np.int32), tokens])[
            -4 * self.PROMPT_BUCKET:]

    def generate(self, tokens: np.ndarray, max_new: int = 16,
                 key: Optional[jax.Array] = None,
                 temperature: float = 0.0) -> np.ndarray:
        toks = jnp.asarray(self.prompt_window(tokens)[None, :])
        cache_len = toks.shape[1] + max_new
        logits, caches = self._prefill(toks, cache_len)
        out = []
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        if key is None:
            key = jax.random.PRNGKey(0)
        for _ in range(max_new):
            out.append(int(cur[0, 0]))
            logits, caches = self._decode(cur, caches)
            key, sub = jax.random.split(key)  # fresh key per sampled token
            cur = sample_token(logits, sub, temperature=temperature)[:, None]
        return np.asarray(out, np.int32)

    def _prefill(self, toks, cache_len: int):
        if not hasattr(self, "_prefill_jit"):
            self._prefill_jit = {}
        key = (toks.shape, cache_len)
        if key not in self._prefill_jit:
            import functools
            self._prefill_jit[key] = jax.jit(functools.partial(
                prefill_forward, cfg=self.cfg, cache_len=cache_len))
        return self._prefill_jit[key](self.params, tokens=toks)

    def _decode(self, cur, caches):
        if not hasattr(self, "_decode_jit"):
            self._decode_jit = jax.jit(
                lambda p, t, c: decode_step(p, self.cfg, t, c))
        return self._decode_jit(self.params, cur, caches)


class SimulatedJudge:
    """Offline reward oracle: quality by (task family, model tier) + noise.
    Profiles mirror the simulator's calibrated matrix (DESIGN.md §4)."""

    PROFILES = {
        # family:     budget  mid   frontier
        "math":       (0.69, 0.84, 0.96),
        "code":       (0.73, 0.86, 0.96),
        "reasoning":  (0.72, 0.85, 0.96),
        "knowledge":  (0.81, 0.985, 0.945),
        "commonsense": (0.87, 0.98, 0.93),
    }
    TIERS = ("budget", "mid", "frontier")

    def __init__(self, seed: int = 0, noise: float = 0.055):
        self.rng = np.random.default_rng(seed)
        self.noise = noise
        self.overrides: Dict[str, float] = {}  # model name -> forced mean

    def score(self, family: str, model: ServedModel) -> float:
        if model.name in self.overrides:
            base = self.overrides[model.name]
        else:
            prof = self.PROFILES.get(family, self.PROFILES["reasoning"])
            base = prof[self.TIERS.index(model.tier)]
        return float(np.clip(base + self.noise * self.rng.standard_normal(),
                             0.0, 1.0))

    def degrade(self, model_name: str, mean: float):
        """Silently regress one model (§4.4 stress test)."""
        self.overrides[model_name] = mean

    def restore(self, model_name: str):
        self.overrides.pop(model_name, None)


@dataclasses.dataclass
class ServeResult:
    request_id: int
    model: str
    arm: int
    reward: float
    cost: float
    tokens_out: int
    route_us: float
    total_ms: float
    lam: float


class PortfolioServer:
    """Closed-loop serving: route -> generate -> judge -> update."""

    def __init__(
        self,
        models: List[ServedModel],
        whitener: PCAWhitener,
        budget: float,
        router_cfg: Optional[RouterConfig] = None,
        judge: Optional[SimulatedJudge] = None,
        max_new_tokens: int = 8,
        seed: int = 0,
        feedback_store=None,
    ):
        self.cfg = router_cfg or RouterConfig()
        self.whitener = whitener
        self.judge = judge or SimulatedJudge(seed)
        self.max_new_tokens = max_new_tokens
        self.models: List[Optional[ServedModel]] = [None] * self.cfg.max_arms
        self._tokenizers: Dict[str, HashTokenizer] = {}  # per-model cache
        self._gen_key = jax.random.PRNGKey(seed ^ 0x5EED)
        prices_req = np.full(self.cfg.max_arms, 1e9, np.float32)
        prices_1k = np.full(self.cfg.max_arms, 1e9, np.float32)
        active = np.zeros(self.cfg.max_arms, bool)
        state: RouterState = init_state(
            self.cfg, prices_req, prices_1k, budget,
            key=jax.random.PRNGKey(seed), active=jnp.asarray(active),
        )
        # The gateway (DESIGN.md §13) owns the double-buffered state, the
        # statics-keyed compiled block functions, the feedback store
        # (context cache for async rewards, §3.6 — in-memory default,
        # SQLiteFeedbackStore for durable multi-worker deployments) and
        # the telemetry counters that used to be ad-hoc attributes here.
        self.gateway = RouterGateway(self.cfg, state, store=feedback_store)
        for i, m in enumerate(models):
            self.add_model(m, slot=i, forced_exploration=False)

    # The live router state and the drop counter read through to the
    # gateway — kept as properties so every pre-gateway caller
    # (tests, examples, benchmarks) keeps working unchanged.
    @property
    def state(self) -> RouterState:
        return self.gateway.live_state

    @property
    def dropped_feedback(self) -> int:
        # Late/duplicate/unknown rewards are skipped, not raised on — the
        # async path faces redelivery and replay; operators watch this.
        return self.gateway.telemetry.counter("dropped_feedback")

    @property
    def _ctx_cache(self):
        return self.gateway.store

    # -- portfolio management (hot swap, §3.6) ------------------------------
    def add_model(self, model: ServedModel, slot: Optional[int] = None,
                  n_eff: float = 0.0, forced_exploration: bool = True) -> int:
        if slot is None:
            slot = next(
                i for i, m in enumerate(self.models)
                if m is None and not bool(self.state.active[i])
            )
        # Model first, state second: the instant the publish lands, a
        # concurrent selection may route to the slot, and the model must
        # already be behind it.
        self.models[slot] = model
        self.gateway.apply_control(lambda s: registry_lib.add_arm(
            self.cfg, s, slot,
            model.pricing.price_per_req, model.pricing.price_per_1k,
            n_eff=n_eff or None, forced_exploration=forced_exploration,
        ))
        return slot

    def remove_model(self, slot: int) -> None:
        # State first, model second — mirror image of add_model: retire
        # the arm through the publish path so no post-publish selection
        # can route here, then drop the model object.
        self.gateway.apply_control(
            lambda s: registry_lib.delete_arm(self.cfg, s, slot))
        self.models[slot] = None

    def set_budget(self, budget: float) -> None:
        from repro.core import pacer
        self.gateway.apply_control(lambda s: dataclasses.replace(
            s, pacer=pacer.set_budget(s.pacer, budget)))

    def set_hyperparams(self, hyper: Optional[HyperParams] = None,
                        **overrides) -> HyperParams:
        """Retune the live router's hyper-parameters with ZERO retraces.

        They live in ``RouterState.hyper`` as traced f32 leaves (DESIGN.md
        §9), so replacing their *values* keeps the state's pytree
        structure — and therefore the jitted select/update programs —
        intact; only a shape/dtype change could force a recompile, and
        this setter cannot produce one. Pass a full ``HyperParams`` or
        field overrides (``srv.set_hyperparams(alpha=0.05)``); values are
        range-validated (ValueError) before they touch the state.
        Returns the now-live concrete ``HyperParams``.
        """
        self.gateway.apply_control(
            lambda s: with_hyperparams(s, hyper=hyper, **overrides))
        return self.hyperparams()

    def hyperparams(self) -> HyperParams:
        """The live hyper-parameters as concrete floats (operator view)."""
        return HyperParams(**{
            n: float(np.asarray(getattr(self.state.hyper, n)))
            for n in HYPER_FIELDS
        })

    def metrics(self) -> Dict[str, float]:
        """Operator metrics, all floats (the typed contract — a TTL-less
        store reports ``store_ttl_s = -1.0``, never ``None``): the legacy
        feedback counters (store depth, dropped/expired feedback) plus
        the gateway telemetry — per-arm pull rates, p50/p95 route
        latency, pacer dual, queue/window gauges, snapshot version."""
        return self.gateway.metrics()

    def prometheus_text(self) -> str:
        """Prometheus exposition-format scrape of the same telemetry."""
        return self.gateway.prometheus_text()

    # -- request path -------------------------------------------------------
    def featurize(self, prompt: str) -> jnp.ndarray:
        raw = jnp.asarray(hash_encode(prompt))
        return self.whitener(raw)

    def featurize_batch(self, prompts: List[str]) -> jnp.ndarray:
        raw = jnp.asarray(hash_encode_batch(prompts))
        return self.whitener(raw)

    def _tokenizer(self, model: ServedModel) -> HashTokenizer:
        tok = self._tokenizers.get(model.name)
        if tok is None or tok.vocab_size != model.cfg.vocab_size:
            tok = HashTokenizer(model.cfg.vocab_size)
            self._tokenizers[model.name] = tok
        return tok

    def serve(self, request: Dict, defer_feedback: bool = False) -> ServeResult:
        """Scalar serving: the B = 1 case of ``serve_batch`` (same jitted
        block functions, same semantics as the original per-request path)."""
        return self.serve_batch([request], defer_feedback=defer_feedback)[0]

    def serve_batch(self, requests: List[Dict],
                    defer_feedback: bool = False) -> List[ServeResult]:
        """Batched serving: featurize the block, route it through the
        backend in one ``select_batch`` call, generate grouped by chosen
        arm (each model stays hot for its share of the block), then feed
        the block's (reward, cost) back through ``update_batch``.

        With ``defer_feedback=True`` the bandit update is left to the
        caller (``feedback``/``feedback_batch``) — the asynchronous
        production path, §3.1: contexts stay cached in the feedback store.
        """
        if not requests:
            return []
        if all(m is None for m in self.models):
            # An all-False candidate mask would argmax into slot 0 — an
            # inactive slot with no model behind it (pacer.py); fail loudly
            # instead of routing into the void. The models list tracks
            # state.active in lockstep (add_model/remove_model), so this
            # guard costs no device round-trip on the hot path.
            raise RuntimeError(
                "empty portfolio: no active arms to route to "
                "(add_model before serving)")
        t0 = time.perf_counter()
        B = len(requests)
        X = self.featurize_batch([r["prompt"] for r in requests])

        # One select_batch through the gateway's selection plane; the
        # (context, routed arm, snapshot version) triple is cached in the
        # feedback store at route time — the async source of truth, so
        # late feedback can omit the arm (§3.1).
        routed = self.gateway.route_block([r["id"] for r in requests], X)
        arms = routed.arms
        route_us = routed.route_us
        lam = routed.lam
        rewards = np.zeros(B, np.float32)
        costs = np.zeros(B, np.float32)
        results: List[Optional[ServeResult]] = [None] * B
        # Group generation by chosen arm (stable order within a group).
        for i in np.argsort(arms, kind="stable"):
            req, arm = requests[int(i)], int(arms[i])
            model = self.models[arm]
            prompt_ids = self._tokenizer(model).encode(req["prompt"])
            self._gen_key, sub = jax.random.split(self._gen_key)
            out = model.generate(prompt_ids, self.max_new_tokens, key=sub)

            n_tokens = len(prompt_ids) + len(out)
            costs[i] = model.pricing.price_per_1k * n_tokens / 1e3
            rewards[i] = self.judge.score(
                req.get("family", "reasoning"), model)
            results[int(i)] = ServeResult(
                request_id=req["id"], model=model.name, arm=arm,
                reward=float(rewards[i]), cost=float(costs[i]),
                tokens_out=len(out), route_us=route_us, total_ms=0.0,
                lam=lam,
            )
        if not defer_feedback:
            self.feedback_batch(
                [r["id"] for r in requests], arms, rewards, costs)
        total_ms = (time.perf_counter() - t0) * 1e3
        return [dataclasses.replace(r, total_ms=total_ms) for r in results]

    def feedback(self, request_id: int, *, reward: float, cost: float,
                 arm: Optional[int] = None) -> None:
        """Asynchronous feedback path: uses the (context, arm) cached at
        route time, so late rewards never re-encode the prompt and the
        caller may omit the arm entirely — the store resolves it (§3.1).

        ``reward``/``cost``/``arm`` are keyword-only: the pre-hardening
        signature was positional ``(request_id, arm, reward, cost)``, and
        an old-style positional call must fail loudly rather than bind an
        arm index as the reward."""
        arms = None if arm is None else np.asarray([arm])
        self.feedback_batch([request_id], arms,
                            np.asarray([reward]), np.asarray([cost]))

    def feedback_batch(self, request_ids: List[int], arms, rewards,
                       costs) -> None:
        """Apply a block of (possibly late) feedback in one fused
        ``update_batch`` call, using the contexts cached at route time.

        Never raises on bad ids: unknown, already-consumed (duplicate or
        replayed) and arm-unresolvable entries are skipped and counted in
        ``dropped_feedback`` — at-least-once reward delivery must not
        crash the gateway. ``arms`` may be None (or carry -1 entries): the
        arm is then resolved from the feedback store's route-time record.
        """
        if not len(request_ids):
            return
        # Resolution, validation and drop accounting live in the
        # gateway's learner plane; the immediate tick (publish cadence 1)
        # reproduces the old inline update exactly.
        if self.gateway.enqueue_feedback(request_ids, arms, rewards, costs):
            self.gateway.learn_tick()

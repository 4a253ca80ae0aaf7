"""Pipeline parallelism from stage actors (DESIGN.md §4).

``make_layer_stage_actors`` slices a model's layer stack into contiguous
stages, each owned by one actor (one mesh slice at pod scale); the
:class:`PipelineRunner` streams microbatches through the stage chain with
a bounded in-flight depth — the paper's async event-chaining (Listing 4)
applied to 1F pipeline schedules: stage *n+1* of microbatch *i* overlaps
stage *n* of microbatch *i+1*.

The stage chain itself is built with the unified
:class:`repro.core.Pipeline` surface (``mode="staged"``), so the same
composition object covers kernel actors and model stages.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import ActorRef, ActorSystem
from repro.core.api import Pipeline
from repro.core.memref import DeviceRef, as_device_array
from repro.models.layers import apply_norm
from repro.models.transformer import embed_inputs, layer_groups, _apply_unit

__all__ = ["PipelineRunner", "make_layer_stage_actors"]


# ----------------------------------------------------------------------------
# stage construction
# ----------------------------------------------------------------------------
def _positions_for(cfg, b: int, s: int):
    base = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return jnp.broadcast_to(base, (3, b, s)) if cfg.m_rope else base


def _stage_fn(model, chunk_units, first: bool, last: bool):
    """A pure ``(stage_params, x) → x`` function for one stage.

    The first stage embeds tokens; the last applies the final norm and LM
    head. Middle stages are pure residual-stream transforms, so only the
    [B, S, D] activation crosses actor boundaries. ``stage_params`` holds
    the stage's layers under ``"units"`` plus whichever of ``embed``,
    ``final_norm`` and ``head`` the stage needs."""
    cfg = model.cfg

    def stage(stage_params, x):
        if first:
            tokens = x
            b, s = tokens.shape
            x = embed_inputs(stage_params, cfg, tokens, None)
        else:
            b, s = x.shape[0], x.shape[1]
        positions = _positions_for(cfg, b, s)
        aux = jnp.zeros((), jnp.float32)
        for unit, lp in zip(chunk_units, stage_params["units"]):
            x, aux = _apply_unit(lp, cfg, unit, x, positions, aux,
                                 model.attn_impl)
        if last:
            x = apply_norm(stage_params["final_norm"], x, cfg.norm)
            h = (stage_params["embed"].T if cfg.tie_embeddings
                 else stage_params["head"])
            return x @ h.astype(x.dtype)
        return x

    return stage


def make_layer_stage_actors(system: ActorSystem, model, params,
                            n_stages: int) -> List[ActorRef]:
    """Split the layer stack into ``n_stages`` contiguous stage actors.

    Stage ``i`` lives on the ``i``-th device the system's
    :class:`~repro.core.manager.DeviceManager` lists (wrapping round when
    there are fewer devices than stages): its parameters are placed there,
    its program runs there, and each incoming activation is moved there.

    The staged forward reproduces ``model.forward`` exactly (same per-layer
    ops in the same order); only the logits (not the MoE aux loss) leave
    the last stage."""
    cfg = model.cfg
    if cfg.family == "encdec":
        raise NotImplementedError("stage split targets decoder-only stacks")
    units: list = []  # (unit kinds, per-layer params)
    for gi, (unit, count) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        for ci in range(count):
            units.append((unit, jax.tree.map(lambda a, ci=ci: a[ci], gp)))
    n_layers = len(units)
    if not 1 <= n_stages <= n_layers:
        raise ValueError(f"n_stages={n_stages} not in [1, {n_layers}]")
    sizes = [n_layers // n_stages + (1 if i < n_layers % n_stages else 0)
             for i in range(n_stages)]
    devices = system.opencl_manager().devices()
    stages, lo = [], 0
    for si, sz in enumerate(sizes):
        chunk = units[lo:lo + sz]
        first, last = si == 0, si == n_stages - 1
        lo += sz
        stage_params = {"units": [p for _, p in chunk]}
        if first or (last and cfg.tie_embeddings):
            stage_params["embed"] = params["embed"]
        if last:
            stage_params["final_norm"] = params["final_norm"]
            if not cfg.tie_embeddings:
                stage_params["head"] = params["head"]
        device = devices[si % len(devices)].jax_device
        stage_params = jax.device_put(stage_params, device)
        jitted = jax.jit(_stage_fn(model, [u for u, _ in chunk],
                                   first=first, last=last))

        # stages speak DeviceRef natively: inputs are unwrapped (host
        # microbatches are transferred once, by the first stage) and the
        # [B, S, D] activation crosses actor boundaries as a ref — the
        # composed chain releases it once the next stage has consumed it
        def _stage(x, _f=jitted, _p=stage_params, _d=device, _last=last):
            y = _f(_p, jax.device_put(as_device_array(x), _d))
            return y if _last else DeviceRef(y)

        stages.append(system.spawn(_stage))
    return stages


# ----------------------------------------------------------------------------
# microbatch streaming
# ----------------------------------------------------------------------------
class PipelineRunner:
    """Streams microbatches through a stage chain with ≤ ``depth`` in
    flight; results come back in submission order and the first stage
    failure aborts the run.

    :meth:`submit` is the asynchronous single-microbatch entry point —
    staged *serving* across layer actors drives it directly (one request's
    activations per call, concurrent up to ``depth``); :meth:`run` is the
    batch-mode loop over it.

    Construction takes either ``stages`` (a linear actor chain, built
    through the :class:`~repro.core.api.Pipeline` wrapper) **or**
    ``graph=`` — a :class:`repro.core.graph.Graph` (built on the fly) or
    an already-built :class:`~repro.core.graph.GraphRef` — so microbatch
    streaming works over arbitrary device-resident DAGs (fan-out/fan-in
    model stages), not just chains.
    """

    def __init__(self, system: ActorSystem,
                 stages: Optional[Sequence[ActorRef]] = None,
                 depth: int = 2, *, graph=None):
        if (stages is None) == (graph is None):
            raise ValueError("pass exactly one of stages or graph")
        self.depth = depth
        if graph is not None:
            from repro.core.graph import Graph
            self._chain = graph.build() if isinstance(graph, Graph) else graph
        else:
            if not stages:
                raise ValueError("need at least one stage")
            self._chain = Pipeline(system, mode="staged").stages(
                stages).build()
        # shared in-flight window: concurrent submit() callers (a serve
        # engine's request threads) and run() draw from the same budget
        self._sem = threading.Semaphore(depth)

    def submit(self, mb: Any, *, emit: str = "value",
               timeout: Optional[float] = None) -> Future:
        """Admit one microbatch into the stage chain; returns a future for
        its result. At most ``depth`` microbatches are in flight — a full
        window blocks the caller (backpressure) until a slot frees, or
        raises ``TimeoutError`` after ``timeout`` seconds.

        ``emit`` selects the result representation:

        * ``"value"`` — whatever the last stage produced (default);
        * ``"ref"``   — wrap each result as a :class:`DeviceRef`, the
          stay-on-device handoff to a downstream consumer;
        * ``"spill"`` — wrap **and spill**: the explicit host-serialization
          stage boundary (paper §3.5 option (b)) for cross-node transport —
          spilled refs pickle.
        """
        if emit not in ("value", "ref", "spill"):
            raise ValueError(f"emit must be value|ref|spill, got {emit!r}")
        if not self._sem.acquire(timeout=timeout):
            raise TimeoutError(
                f"pipeline in-flight window ({self.depth}) still full "
                f"after {timeout}s")
        payload = mb if isinstance(mb, tuple) else (mb,)
        try:
            fut = self._chain.request(*payload)
        except BaseException:
            # the window is instance state now: a synchronous request
            # failure must hand its slot back or the runner shrinks
            self._sem.release()
            raise
        out: Future = Future()

        def _done(f):
            self._sem.release()
            exc = f.exception()
            if exc is not None:
                out.set_exception(exc)
                return
            res = f.result()
            if emit != "value":
                ref = (res if isinstance(res, DeviceRef)
                       else DeviceRef(jnp.asarray(res)))
                if emit == "spill":
                    ref.spill()
                res = ref
            out.set_result(res)

        fut.add_done_callback(_done)
        return out

    def run(self, microbatches: Sequence[Any],
            timeout: Optional[float] = 300.0, emit: str = "value") -> list:
        """Stream the microbatches; returns results in submission order.

        Microbatches may be host arrays **or** :class:`DeviceRef`\\ s (the
        first stage unwraps refs, so data already on device never bounces
        through the host). A thin loop over :meth:`submit`; the first
        stage failure stops further admissions and aborts the run.
        """
        futures: list[Future] = []
        for mb in microbatches:
            if any(f.done() and f.exception() is not None for f in futures):
                break  # a stage already failed: stop admitting
            futures.append(self.submit(mb, emit=emit, timeout=timeout))
        results: list = [None] * len(microbatches)
        first_error: Optional[BaseException] = None
        for i, f in enumerate(futures):
            try:
                results[i] = f.result(timeout)
            except Exception as exc:
                if first_error is None:
                    first_error = exc
        if first_error is not None:
            raise first_error
        return results

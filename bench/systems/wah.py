"""System under test: WAH bitmap-index builds on kernel actors (paper §4).

An ask to a kernel actor whose kernel is
``repro.indexing.build_wah_index`` (the default implementation, so the
Pallas kernels run on a TPU). The input is a resident ``DeviceRef``; the
index words stay on the device as a ``DeviceRef`` and the word count and
lookup table are read back.

The values are the configuration's: ``cardinality`` distinct values with
Zipf-skewed frequencies (``zipf_exponent``), drawn from the seed on the
device. A ring of ``ring`` distinct batches is made during set-up, and
request ``i`` reads batch ``i % ring``.

Traffic keys read here: ``values_per_call``, ``ring``, and the request
field ``batch``.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench import load_module
from bench.generator import no_span


def make_values(config: dict, n: int, ring: int, seed: int):
    """``ring`` batches of ``n`` values, on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    card = int(config["cardinality"])
    ranks = np.arange(1, card + 1, dtype=np.float64)
    p = ranks ** -float(config["zipf_exponent"])
    cdf = np.cumsum(p) / p.sum()
    words = np.random.SeedSequence(seed).generate_state(2)

    @jax.jit
    def make(words):
        # the seed is an argument, so every seed runs the one cached program
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        u = jax.random.uniform(key, (ring, n), jnp.float32)
        v = jnp.searchsorted(jnp.asarray(cdf[:-1], jnp.float32), u,
                             side="right")
        return v.astype(jnp.uint32)

    batches = make(jnp.asarray(words & 0x7FFFFFFF, jnp.int32))
    return [batches[i] for i in range(ring)]


class System:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 span=no_span):
        import jax.numpy as jnp
        from repro.core import (ActorSystem, DeviceRef, In, NDRange, Out,
                                dim_vec, kernel)
        from repro.indexing import build_wah_index

        self.config, self.traffic, self.seed = config, traffic, seed
        self.ref = load_module("reference", config["reference"])
        self.card = int(config["cardinality"])
        n = int(traffic["values_per_call"])
        ring = int(traffic["ring"])
        values = make_values(config, n, ring, seed)
        self.host_values = [np.asarray(v) for v in values]
        self.actors = ActorSystem(name="bench-wah")
        card = self.card

        @kernel(In(jnp.uint32), Out(jnp.uint32, as_ref=True),
                Out(jnp.int32), Out(jnp.int32), Out(jnp.int32),
                nd_range=NDRange(dim_vec(n)), name="wah_build")
        def wah_build(v):
            return build_wah_index(v, card)

        self.actor = self.actors.spawn(wah_build)
        self.inputs = [(DeviceRef(v),) for v in values]
        self.calls = 0
        self.release_out(self.issue({"batch": 0})["out"])   # compiles

    def issue(self, req: dict) -> dict:
        b = int(req["batch"])
        out = self.actor.ask(*self.inputs[b], timeout=600)
        self.calls += 1
        return {"batch": b, "out": out}

    @staticmethod
    def release_out(out) -> None:
        from repro.core import DeviceRef
        for x in out:
            if isinstance(x, DeviceRef):
                x.release()

    def counters(self) -> dict:
        return {"calls": self.calls}

    def release(self) -> None:
        self.actors.shutdown()

    def check(self, records: List[dict]) -> List[tuple]:
        """Every answer against the plain reference of its batch, word for
        word: the words past the count must be zero, and the lookup table
        must match."""
        from repro.core import DeviceRef

        want = {}
        bad_calls = bad_words = 0
        for r in records:
            if not r.get("ok"):
                continue
            b = r["batch"]
            if b not in want:
                want[b] = self.ref.wah_index(self.host_values[b], self.card)
            words, starts, counts = want[b]
            out = r.pop("out")
            got_words, n_words, got_starts, got_counts = out
            got = got_words.to_value()
            self.release_out(out)
            table_ok = (np.array_equal(got_starts, starts)
                        and np.array_equal(got_counts, counts))
            n_words = int(n_words)
            k = min(n_words, words.shape[0])
            wrong = int(np.count_nonzero(got[:k] != words[:k])
                        + abs(n_words - words.shape[0])
                        + np.count_nonzero(got[n_words:]))
            bad_words += wrong
            bad_calls += int(wrong > 0 or not table_ok)
        return [("wrong_words", bad_words, 0), ("wrong_calls", bad_calls, 0),
                ("checked_calls", sum(1 for r in records if r.get("ok")),
                 None)]

    def control(self, records: List[dict]) -> List[tuple]:
        """The control: the reference with an unstable sort (equal values'
        positions out of order) in the program's place, against the plain
        reference, on the batches the window used."""
        wrong = 0
        for b in sorted({r["batch"] for r in records if r.get("ok")}):
            hv = self.host_values[b]
            want, _, _ = self.ref.wah_index(hv, self.card)
            got, _, _ = self.ref.wah_index(hv, self.card, stable=False)
            k = min(want.shape[0], got.shape[0])
            wrong += int(np.count_nonzero(want[:k] != got[:k])
                         + abs(want.shape[0] - got.shape[0]))
        return [("wrong_words", wrong, 0)]

    def close(self) -> None:
        self.inputs = []

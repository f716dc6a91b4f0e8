"""Entry ``execute_rw``: each round is one call of the op engine's mixed
read/write closure, ``ShardedDHT.execute_fn(("read", "write"))``, with the
table state threaded through by the harness.

Preload (all rows tagged write) and read-back (all rows tagged read) go
through the same compiled closure, so the cell compiles one program."""
from __future__ import annotations

import numpy as np


class Entry:
    fields = ("keys", "vals", "op")

    def __init__(self, dht, workload: dict):
        from repro.core import OP_READ, OP_WRITE

        self.dht = dht
        self.fn = dht.execute_fn(("read", "write"))
        self.tags = {"read": OP_READ, "write": OP_WRITE}
        self._const: dict = {}

    def op_tags(self, is_write: np.ndarray) -> np.ndarray:
        return np.where(is_write, self.tags["write"],
                        self.tags["read"]).astype(np.int32)

    def _all(self, kind: str, like):
        """A constant op-tag array shaped and placed like ``like``."""
        key = (kind, like.shape)
        if key not in self._const:
            import jax
            import jax.numpy as jnp

            self._const[key] = jax.device_put(
                jnp.full(like.shape, self.tags[kind], jnp.int32),
                like.sharding)
        return self._const[key]

    def _call(self, keys, vals, valid, op):
        self.dht.state, out, found, code, es = self.fn(
            self.dht.state, keys, vals, valid, op)
        return out, found, code, es

    def write_rows(self, keys, vals, valid):
        return self._call(keys, vals, valid, self._all("write", valid))[2]

    def read_rows(self, keys, valid, vals):
        """``vals`` only fills the value lane the closure always carries."""
        out, found, _, _ = self._call(keys, vals, valid,
                                      self._all("read", valid))
        return found, out

    def round(self, b: dict) -> dict:
        out, found, code, es = self._call(b["keys"], b["vals"], b["valid"],
                                          b["op"])
        return {"found": found, "vals": out, "code": code,
                "dropped": es["dropped"]}

    def live(self):
        return self.dht.state

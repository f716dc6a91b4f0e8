"""Entry ``write``: each round is one ``ShardedDHT.write`` of the batch.

A round includes the wrapper's host work: the ``int(jnp.sum(retry))``
sync that decides whether dropped rows are re-issued, and its
``record_round``.  Preload goes through the same call; the read-back
after the window through ``ShardedDHT.read`` at the same batch."""
from __future__ import annotations


class Entry:
    fields = ("keys", "vals")

    def __init__(self, dht, workload: dict):
        self.dht = dht

    def write_rows(self, keys, vals, valid):
        return self.dht.write(keys, vals, valid)["code"]

    def read_rows(self, keys, valid, vals=None):
        vals, found, _ = self.dht.read(keys, valid)
        return found, vals

    def round(self, b: dict) -> dict:
        st = self.dht.write(b["keys"], b["vals"], b["valid"])
        return {"code": st["code"], "dropped": st["dropped"]}

    def live(self):
        return self.dht.state

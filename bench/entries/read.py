"""Entry ``read``: each round is one ``ShardedDHT.read`` of the batch.

The table is preloaded and read back through ``ShardedDHT.write`` /
``ShardedDHT.read`` at the cell's batch.  A round includes the host work
the wrapper does (its ``record_round`` fetch of the stat lanes)."""
from __future__ import annotations


class Entry:
    fields = ("keys",)

    def __init__(self, dht, workload: dict):
        self.dht = dht

    def write_rows(self, keys, vals, valid):
        return self.dht.write(keys, vals, valid)["code"]

    def read_rows(self, keys, valid, vals=None):
        vals, found, _ = self.dht.read(keys, valid)
        return found, vals

    def round(self, b: dict) -> dict:
        vals, found, stats = self.dht.read(b["keys"], b["valid"])
        return {"found": found, "vals": vals, "dropped": stats["dropped"]}

    def live(self):
        return self.dht.state

"""``route_kernel_roofline`` (%): the least time the route pack/unpack
kernels could take for the bytes the round's ops must move, at the chip's
peak HBM bandwidth, over the kernels' summed device time.

The bytes come from the ops' payload, not from the kernels' padded
shapes, so a rewrite of the kernels is judged on the same work: per op,
the words it sends (key; value and op tag where the op carries them) and
the words that come back (value and found flag for a read, the code for a
write), 4 B each, read once and written once by the kernel that moves
them.  Memory bound: the kernels do no arithmetic on the words.

Only the ops that the round routes count.  A read-only round leaves the
rows its own shard owns out of the routing (``op_engine.dht_issue``
elides self-owned traffic), so the kernels move only fill rows for them:
at one shard every row of such a round is elided, the bytes are 0 and the
metric reads nothing.  At S shards a uniform read round routes an
expected (S - 1) / S of its rows.  Mixed and write rounds route every row.
"""
from __future__ import annotations

from harness import devtrace

KERNELS = ("route_pack_pallas", "route_unpack_pallas")


def is_route_kernel(label: str) -> bool:
    """The kernels' HLO instructions carry their jitted wrappers' names
    (``route_pack_pallas.1 custom-call ...``)."""
    return label.split(".")[0].split(" ")[0] in KERNELS


def payload_words(table: dict, mix: dict) -> tuple[float, float]:
    """(send, reply) words per op for a round with this read/write mix."""
    kw, vw = table["key_words"], table["val_words"]
    r, w = float(mix["read"]), float(mix["write"])
    mixed = r > 0 and w > 0
    tag = 1 if mixed else 0
    send = r * (kw + tag) + w * (kw + vw + tag)
    reply = r * (vw + 1) + w * 1
    return send, reply


def routed_share(table: dict, mix: dict) -> float:
    """Share of a round's ops that go through the route kernels."""
    if float(mix["write"]) > 0:
        return 1.0
    shards = int(table.get("n_shards", 1))
    return (shards - 1) / shards


def round_bytes(table: dict, mix: dict, batch: int) -> float:
    """Bytes the route kernels must read and write for one round."""
    send, reply = payload_words(table, mix)
    return 2.0 * 4.0 * batch * routed_share(table, mix) * (send + reply)


def read(ctx):
    secs = sum(devtrace.op_seconds(ctx.trace, is_route_kernel).values())
    bw = ctx.peaks.get("hbm_bytes_per_s")
    need = round_bytes(ctx.table, ctx.workload["mix"], ctx.batch) * ctx.rounds
    if secs <= 0 or not bw or need <= 0:
        return None
    return 100.0 * (need / bw) / secs

"""Plain reference for a cache-semantics key-value table.

The guarantees it holds a table to (stated in each configuration file):

- a write is acknowledged with a code: update when the key is held,
  insert or evict when it is not; evict means the write overwrote some
  other key, which is then lost;
- a read sees the table as of the start of its round, and finds the value
  of the key's last write, unless a key was lost;
- within one round, the write with the highest batch index wins;
- no op is dropped, and no more keys go missing than evictions were
  reported.

The reference works in id space: per id, whether it is held and the stamp
of its last write; ``bench/harness/traffic.py`` turns an id into its key
words and a stamp into its value words, with nothing taken from the
program.  A value read back is judged by the stamp it claims (its word 0)
and by whether all its words are that stamp's value (``whole``, worked
out on the device by ``traffic.value_check``): together, whether it is
the value of the key's last write.  It is driven round by round with what the table answered and
counts every departure; :meth:`Reference.checks` returns the numbers that
decide ``correct``, each with its limit.
"""
from __future__ import annotations

import numpy as np

# the program's write codes (DESIGN.md §8), restated, not imported
W_DROPPED, W_INSERT, W_UPDATE, W_EVICT = 0, 1, 2, 3


def _last_per_id(ids: np.ndarray, vals: np.ndarray):
    """Unique ids and, per id, the value at its highest position."""
    u, first = np.unique(ids[::-1], return_index=True)
    return u, vals[::-1][first]


class Reference:
    def __init__(self, n_ids: int):
        self.held = np.zeros(n_ids, bool)
        self.stamp = np.zeros(n_ids, np.uint32)
        self.evictions = 0
        self.losses = 0           # keys found missing that the table held
        self.wrong_values = 0     # found with a value that is not the last write
        self.found_absent = 0     # found although no write put it there
        self.wrong_codes = 0      # a write code the semantics do not allow
        self.dropped = 0          # writes acked with the drop code
        self.compared_values = 0
        self.compared_ops = 0

    # -- writes -----------------------------------------------------------
    def write(self, ids, stamps, code) -> None:
        """One round's writes, in batch order, with their acked codes.

        Codes are judged against the round's snapshot; the held state is
        then advanced by the write with the highest batch index per id."""
        ids = np.asarray(ids, np.int64)
        code = np.asarray(code)
        held = self.held[ids]
        self.compared_ops += ids.size
        self.dropped += int((code == W_DROPPED).sum())
        ok = np.where(held, code == W_UPDATE,
                      (code == W_INSERT) | (code == W_EVICT))
        # an insert or evict for a held key reveals that the key was lost
        reveal = held & ((code == W_INSERT) | (code == W_EVICT))
        self.losses += int(np.unique(ids[reveal]).size)
        self.wrong_codes += int((~ok & ~reveal & (code != W_DROPPED)).sum())
        self.evictions += int((code == W_EVICT).sum())
        applied = code != W_DROPPED
        u, s = _last_per_id(ids[applied], np.asarray(stamps)[applied])
        self.held[u] = True
        self.stamp[u] = s

    # -- reads ------------------------------------------------------------
    def read(self, ids, found, stamp=None, whole=None) -> None:
        """One round's reads against the snapshot: presence always, values
        where ``stamp`` and ``whole`` (``traffic.value_check`` of the values
        read) are given.  A miss of a held key is a loss (counted once per
        id, which is then no longer held)."""
        ids = np.asarray(ids, np.int64)
        found = np.asarray(found, bool)
        held = self.held[ids]
        self.compared_ops += ids.size
        self.found_absent += int((found & ~held).sum())
        miss = held & ~found
        lost = np.unique(ids[miss])
        self.losses += lost.size
        if stamp is not None:
            chk = found & held
            ok = (np.asarray(stamp)[chk] == self.stamp[ids[chk]]) & \
                np.asarray(whole, bool)[chk]
            self.wrong_values += int((~ok).sum())
            self.compared_values += int(chk.sum())
        self.held[lost] = False

    def round(self, ids, is_write, stamps, found=None, code=None,
              stamp=None, whole=None) -> None:
        """A mixed round: reads see the snapshot, then writes apply."""
        is_write = np.asarray(is_write, bool)
        r = ~is_write
        if r.any():
            self.read(ids[r], found[r],
                      None if stamp is None else stamp[r],
                      None if whole is None else whole[r])
        if is_write.any():
            self.write(ids[is_write], stamps[is_write], code[is_write])

    # -- verdict ----------------------------------------------------------
    def checks(self, dropped_reported: int = 0) -> dict:
        """Numbers compared, each ``{"value": v, "limit": l}``; the run is
        correct when every value is at or under its limit.
        ``dropped_reported`` is the sum of the program's own ``dropped``
        lane over the rounds (reads that overflowed show only there)."""
        unexplained = max(self.losses - self.evictions, 0)
        return {
            "wrong_values": {"value": self.wrong_values, "limit": 0},
            "found_absent": {"value": self.found_absent, "limit": 0},
            "wrong_codes": {"value": self.wrong_codes, "limit": 0},
            "dropped_codes": {"value": self.dropped, "limit": 0},
            "dropped_reported": {"value": int(dropped_reported), "limit": 0},
            "unexplained_losses": {"value": unexplained, "limit": 0},
        }

    def summary(self) -> dict:
        return {"losses": self.losses, "evictions": self.evictions,
                "compared_ops": self.compared_ops,
                "compared_values": self.compared_values}

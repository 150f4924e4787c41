"""The plain reference: which connected peers a LocalMessage reaches.

numpy over the seeded rows, float64 throughout, sharing no code with
the program's quantizer or index. The cube of a coordinate is upstream's
own rule (`coord_clamp`, worldql_server `subscriptions/cube_area.rs:23-44`,
SURVEY.md section 2 component 11): cubes are labelled by their
max-corner multiple of the size, sign-symmetric, so

    c > 0   label = ceil(c / size) * size        (label - size, label]
    c < 0   label = -ceil(|c| / size) * size     [label, label + size)
    c == 0  label = +size

(`cube_area.rs:102-175` lists expected values; `tests/test_reference.py`
holds this function to them.)
"""

from __future__ import annotations

import numpy as np

_BITS = 16                      # per-axis room in the packed key
_OFF = 1 << (_BITS - 1)


def cube_labels(coords, size: int, dtype=np.float64) -> np.ndarray:
    """float coordinates -> int64 cube labels, computed in `dtype`.
    float64 is the reference; float32 is the lower-precision control
    that `correct` has to fail."""
    c = np.asarray(coords, dtype=np.float64).astype(dtype)
    s = dtype(size)
    lab = np.ceil(np.abs(c) / s) * s
    lab = np.where(c == 0, s, lab)
    return (lab * np.where(c < 0, -1, 1)).astype(np.int64)


def pack_keys(wid, labels, size: int) -> np.ndarray:
    """(world id, cube label x/y/z) -> one int64 key each."""
    cell = np.asarray(labels, np.int64) // size + _OFF
    if cell.size and (cell.min() < 0 or cell.max() >= 1 << _BITS):
        raise ValueError("cube label outside the packed key's range")
    wid = np.asarray(wid, np.int64)
    return ((wid << (3 * _BITS)) | (cell[:, 0] << (2 * _BITS))
            | (cell[:, 1] << _BITS) | cell[:, 2])


class ConnectedIndex:
    """Connected peers grouped by (world, cube). Only connected peers
    can receive, so the reference index holds their rows alone."""

    def __init__(self, wid, positions, size: int):
        self.size = size
        keys = pack_keys(wid, cube_labels(positions, size), size)
        self.order = np.argsort(keys, kind="stable")
        self.keys, starts = np.unique(keys[self.order], return_index=True)
        self.starts = np.append(starts, len(keys))

    def expected(self, wid, positions, sender, including_self,
                 dtype=np.float64):
        """-> (message index, peer index) of every delivery the
        messages are owed, as two int64 arrays. `sender` is the
        connected-peer index of each message's sender."""
        keys = pack_keys(wid, cube_labels(positions, self.size, dtype),
                         self.size)
        g = np.searchsorted(self.keys, keys)
        g_ok = np.minimum(g, len(self.keys) - 1)
        hit = self.keys[g_ok] == keys
        n = np.where(hit, self.starts[g_ok + 1] - self.starts[g_ok], 0)
        msg = np.repeat(np.arange(len(keys)), n)
        first = np.repeat(self.starts[g_ok], n)
        within = np.arange(len(msg)) - np.repeat(np.cumsum(n) - n, n)
        peer = self.order[first + within]
        keep = (peer != np.asarray(sender)[msg]) | np.asarray(
            including_self, bool)[msg]
        return msg[keep], peer[keep]


def compare(exp_msg, exp_peer, got_msg, got_peer, n_peers: int) -> dict:
    """Multiset comparison of owed and received deliveries.
    -> counts, and for each received delivery whether it is the first
    arrival of an owed one (`good`)."""
    exp = np.unique(np.asarray(exp_msg, np.int64) * n_peers + exp_peer)
    got = np.asarray(got_msg, np.int64) * n_peers + got_peer
    order = np.argsort(got, kind="stable")
    sg = got[order]
    firsts = np.ones(len(sg), bool)
    firsts[1:] = sg[1:] != sg[:-1]
    owed = np.isin(sg, exp, assume_unique=False)
    good_sorted = firsts & owed
    good = np.empty(len(sg), bool)
    good[order] = good_sorted
    return {
        "attempted": int(len(exp)),
        "missing": int(len(exp) - good_sorted.sum()),
        "extra": int((firsts & ~owed).sum()),
        "duplicated": int((~firsts).sum()),
        "good": good,
    }

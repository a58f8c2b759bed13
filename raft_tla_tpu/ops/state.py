"""Fixed-width tensor state schema — the L1 layer (SURVEY §7.0.1).

The spec's 13 non-history variables (``raft.tla:50-86`` plus ``messages``,
``raft.tla:32``) map to a struct of small int32 arrays; a whole state also
round-trips to a flat ``int32[W]`` vector (the frontier storage / fingerprint
form).  History variables (``elections`` ``raft.tla:39``, ``allLogs``
``raft.tla:44``, ``voterLog`` ``raft.tla:77``) are proof-only — read by no
guard — and are stripped in parity mode (SURVEY §7.0.3).

Struct fields (n = servers, L = log capacity, S = message slots):

==============  ========  =====================================================
field           shape     spec variable
==============  ========  =====================================================
role            (n,)      ``state``        (raft.tla:52)  0/1/2 = F/C/L
term            (n,)      ``currentTerm``  (raft.tla:50)
votedFor        (n,)      ``votedFor``     (raft.tla:55)  0 = Nil, else id+1
commitIndex     (n,)      ``commitIndex``  (raft.tla:63)
logLen          (n,)      ``Len(log[i])``  (raft.tla:61)
logTerm         (n, L)    ``log[i][k].term``  (1-based k -> column k-1)
logVal          (n, L)    ``log[i][k].value``  (values 1..V; 0 = no entry)
vResp           (n,)      ``votesResponded`` (raft.tla:69) as bitmask
vGrant          (n,)      ``votesGranted``   (raft.tla:72) as bitmask
nextIndex       (n, n)    ``nextIndex``    (raft.tla:82)
matchIndex      (n, n)    ``matchIndex``   (raft.tla:85)
msgHi/Lo/Count  (S,)      the ``messages`` bag (raft.tla:32), ops/msgbits.py
==============  ========  =====================================================

Canonical form (required before fingerprinting — the bag is unordered, and
sequences are padded):

- message slots sorted by (occupied-first, hi, lo); empty slots are all-zero;
- log columns >= logLen[i] are zero;
- everything else is canonical by construction (bitmask sets, dense arrays).

All transition kernels preserve canonical zero-padding functionally, and
:func:`canonicalize` restores slot order after bag mutations.

The module is dual-backend: every function takes the array namespace ``xp``
(``numpy`` or ``jax.numpy``) so the host oracle and the device kernels share
one implementation, bit for bit.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.models.spec import FOLLOWER, NIL

STATE_FIELDS = ("role", "term", "votedFor", "commitIndex", "logLen",
                "logTerm", "logVal", "vResp", "vGrant",
                "nextIndex", "matchIndex", "msgHi", "msgLo", "msgCount")

# Faithful-mode extras (SURVEY §7.0.3b), appended after the parity fields so
# parity-mode vectors are untouched.  Log-valued data is stored as ranks in
# the bounded log universe (ops/loguniv.py):
#   allLogs  (Wa,)   U-bit bitmask of log ranks        (raft.tla:44)
#   vLog     (n, n)  voterLog[i][j] as rank+1, 0 = absent (raft.tla:77)
#   eTerm    (E,)    elections slots (raft.tla:39); 0 = empty slot
#   eLeader  (E,)    eleader (server id; 0 when slot empty)
#   eLog     (E,)    elog as rank
#   eVotes   (E,)    evotes as a server bitmask
#   eVLog    (E, n)  evoterLog[j] as rank+1, 0 = absent
HISTORY_FIELDS = ("allLogs", "vLog", "eTerm", "eLeader", "eLog",
                  "eVotes", "eVLog")


@dataclasses.dataclass(frozen=True)
class Layout:
    """Shapes and flat-vector offsets for a bounds instance."""

    n: int
    L: int
    S: int
    E: int = 0       # elections slots (faithful mode; 0 = parity mode)
    Wa: int = 0      # allLogs bitmask words

    @classmethod
    def of(cls, bounds: Bounds) -> "Layout":
        if not bounds.history:
            return cls(n=bounds.n_servers, L=bounds.log_cap,
                       S=bounds.msg_cap)
        from raft_tla_tpu.ops.loguniv import LogUniverse
        return cls(n=bounds.n_servers, L=bounds.log_cap, S=bounds.msg_cap,
                   E=bounds.max_elections,
                   Wa=LogUniverse.of(bounds).mask_words)

    @property
    def history(self) -> bool:
        return self.E > 0

    @property
    def shapes(self) -> dict:
        n, L, S, E = self.n, self.L, self.S, self.E
        out = {
            "role": (n,), "term": (n,), "votedFor": (n,),
            "commitIndex": (n,), "logLen": (n,),
            "logTerm": (n, L), "logVal": (n, L),
            "vResp": (n,), "vGrant": (n,),
            "nextIndex": (n, n), "matchIndex": (n, n),
            "msgHi": (S,), "msgLo": (S,), "msgCount": (S,),
        }
        if self.history:
            out.update({
                "allLogs": (self.Wa,), "vLog": (n, n),
                "eTerm": (E,), "eLeader": (E,), "eLog": (E,),
                "eVotes": (E,), "eVLog": (E, n),
            })
        return out

    @property
    def fields(self) -> tuple:
        return STATE_FIELDS + (HISTORY_FIELDS if self.history else ())

    @property
    def width(self) -> int:
        return sum(int(np.prod(s)) for s in self.shapes.values())

    def offset(self, field: str) -> int:
        """Position of ``field``'s first word in the flat vector."""
        off = 0
        for f, shape in self.shapes.items():
            if f == field:
                return off
            off += int(np.prod(shape))
        raise KeyError(field)


def init_struct(bounds: Bounds, xp):
    """The unique initial state (``Init``, ``raft.tla:155-160``).

    currentTerm = 1, state = Follower, votedFor = Nil (``raft.tla:143-145``);
    empty vote sets (``raft.tla:146-147``); nextIndex = 1, matchIndex = 0
    (``raft.tla:151-152``); empty logs, commitIndex = 0 (``raft.tla:153-154``);
    empty message bag (``raft.tla:155``).
    """
    lay = Layout.of(bounds)
    n, L, S = lay.n, lay.L, lay.S
    i32 = xp.int32
    out = {
        "role": xp.full((n,), FOLLOWER, dtype=i32),
        "term": xp.ones((n,), dtype=i32),
        "votedFor": xp.full((n,), NIL, dtype=i32),
        "commitIndex": xp.zeros((n,), dtype=i32),
        "logLen": xp.zeros((n,), dtype=i32),
        "logTerm": xp.zeros((n, L), dtype=i32),
        "logVal": xp.zeros((n, L), dtype=i32),
        "vResp": xp.zeros((n,), dtype=i32),
        "vGrant": xp.zeros((n,), dtype=i32),
        "nextIndex": xp.ones((n, n), dtype=i32),
        "matchIndex": xp.zeros((n, n), dtype=i32),
        "msgHi": xp.zeros((S,), dtype=i32),
        "msgLo": xp.zeros((S,), dtype=i32),
        "msgCount": xp.zeros((S,), dtype=i32),
    }
    if lay.history:
        # InitHistoryVars (raft.tla:140-142): elections = {}, allLogs = {},
        # voterLog = per-server empty map.
        n, E, Wa = lay.n, lay.E, lay.Wa
        out.update({
            "allLogs": xp.zeros((Wa,), dtype=i32),
            "vLog": xp.zeros((n, n), dtype=i32),
            "eTerm": xp.zeros((E,), dtype=i32),
            "eLeader": xp.zeros((E,), dtype=i32),
            "eLog": xp.zeros((E,), dtype=i32),
            "eVotes": xp.zeros((E,), dtype=i32),
            "eVLog": xp.zeros((E, n), dtype=i32),
        })
    return out


def fields_of(struct) -> tuple:
    """A struct's field names in packed order (parity then history)."""
    return STATE_FIELDS + (HISTORY_FIELDS if "allLogs" in struct else ())


def pack(struct, xp):
    """Struct -> flat int32[W] vector (field order = :func:`fields_of`,
    row-major inside a field)."""
    return xp.concatenate([xp.reshape(struct[f], (-1,))
                           for f in fields_of(struct)])


def unpack(vec, lay: Layout, xp):
    """int32[..., W] vector(s) -> struct (leading batch dims pass
    through: [W] -> per-field ``shape``, [C, W] -> ``(C,) + shape``)."""
    out, off = {}, 0
    batch = tuple(vec.shape[:-1])
    for f, shape in lay.shapes.items():
        size = int(np.prod(shape))
        out[f] = xp.reshape(vec[..., off:off + size],
                            batch + tuple(shape)).astype(xp.int32)
        off += size
    return out


def _oddeven_pairs(m: int) -> tuple:
    """Odd-even transposition sorting-network comparator pairs for ``m``
    slots — a data-independent sort: m rounds of adjacent compare-swaps."""
    return tuple((i, i + 1) for r in range(m)
                 for i in range(r % 2, m - 1, 2))


def _network_sort(keys: list, vals: list, m: int, xp):
    """Sort ``m`` slots by the lexicographic key tuple via a branchless
    comparator network; returns the reordered ``vals``.

    Bit-identical to a lexsort-based gather: the key tuples are either
    strictly ordered (occupied slots always differ, see callers) or the
    full rows are identical (empty slots), so every correct sort yields
    the same sequence.  A network of selects is what the orbit pass needs:
    under ``lax.scan`` over the permutation group, a vmapped ``lexsort``
    in the loop body was ~90% of the whole symmetry cost (measured on
    TPU, round 2), while compare-swaps fuse into the surrounding
    elementwise work.
    """
    # Keys and vals overlap (e.g. hi/lo are both); swap each distinct
    # array once per comparator, not once per appearance.
    arrs: list = []
    pos: dict = {}
    for a in list(keys) + list(vals):
        if id(a) not in pos:
            pos[id(a)] = len(arrs)
            arrs.append(a)
    key_ix = [pos[id(k)] for k in keys]
    val_ix = [pos[id(v)] for v in vals]
    for i, j in _oddeven_pairs(m):
        le = None       # key[i] <= key[j], built least-significant first
        for kx in reversed(key_ix):
            k = arrs[kx]
            if le is None:
                le = k[..., i] <= k[..., j]
            else:
                le = (k[..., i] < k[..., j]) | ((k[..., i] == k[..., j]) & le)
        for a_i, a in enumerate(arrs):
            ai, aj = a[..., i], a[..., j]
            arrs[a_i] = a.at[..., i].set(xp.where(le, ai, aj)) \
                .at[..., j].set(xp.where(le, aj, ai)) \
                if xp is not np else _np_swap(a, i, j, le)
    return [arrs[ix] for ix in val_ix]


def _np_swap(a, i: int, j: int, le):
    out = a.copy()
    out[..., i] = np.where(le, a[..., i], a[..., j])
    out[..., j] = np.where(le, a[..., j], a[..., i])
    return out


def canonicalize(struct, xp):
    """Sort message slots into canonical order: occupied first, then (hi, lo)
    (:func:`canonicalize_bag`) and, in faithful mode, the ``elections`` slots
    likewise (:func:`canonicalize_elections`)."""
    out = canonicalize_bag(struct, xp)
    if "eTerm" in struct:
        out.update(canonicalize_elections(struct, xp))
    return out


def canonicalize_bag(struct, xp):
    """The struct with its message slots in canonical order.

    The bag is an unordered function (``raft.tla:32``); slot order is an
    encoding artifact and must not influence the fingerprint.  Distinct
    occupied slots always differ in (hi, lo) — the bag merges equal messages
    into one multiplicity (``WithMessage``, ``raft.tla:106-110``) — so the
    sort is a total order and canonicalization is unique (the comparator
    network in :func:`_network_sort` therefore reproduces the historical
    lexsort bit-for-bit).
    """
    occupied = struct["msgCount"] > 0
    # Enforce, not just assume, the all-zero empty-slot form: a kernel that
    # decrements a count to 0 may leave stale content words behind, which
    # would split fingerprints of identical bags.
    hi = xp.where(occupied, struct["msgHi"], 0)
    lo = xp.where(occupied, struct["msgLo"], 0)
    ct = xp.where(occupied, struct["msgCount"], 0)
    M = int(struct["msgHi"].shape[-1])
    occ_key = (~occupied).astype(xp.int32)
    out = dict(struct)
    out["msgHi"], out["msgLo"], out["msgCount"] = _network_sort(
        [occ_key, hi, lo], [hi, lo, ct], M, xp)
    return out


def canonicalize_elections(struct, xp) -> dict:
    """The five ``elections`` fields of a faithful-mode struct in canonical
    slot order.  elections is a set (raft.tla:39); slot order is an encoding
    artifact, canonicalized exactly like the message bag.  eTerm > 0 marks
    occupancy (election terms start at 1, raft.tla:143)."""
    eocc_key = (~(struct["eTerm"] > 0)).astype(xp.int32)
    E = int(struct["eTerm"].shape[-1])
    evl_cols = [struct["eVLog"][..., c]
                for c in range(struct["eVLog"].shape[-1])]
    keys = [eocc_key, struct["eTerm"], struct["eLeader"],
            struct["eLog"], struct["eVotes"]] + evl_cols
    sorted_vals = _network_sort(
        keys, [struct["eTerm"], struct["eLeader"], struct["eLog"],
               struct["eVotes"]] + evl_cols, E, xp)
    out = dict(zip(("eTerm", "eLeader", "eLog", "eVotes"), sorted_vals[:4]))
    out["eVLog"] = xp.stack(sorted_vals[4:], axis=-1)
    return out


def occupied_slots(struct, xp):
    """Mask of slots holding a bag element (``m \\in DOMAIN messages``)."""
    return struct["msgCount"] > 0


def constraint_ok(struct, bounds: Bounds, xp):
    """The StateConstraint (SURVEY §0 defect 2): scalar bool.

    ``/\\ \\A i : currentTerm[i] <= MaxTerm /\\ Len(log[i]) <= MaxLogLen
    /\\ Cardinality(DOMAIN messages) <= MaxMsgs /\\ \\A m : messages[m] <= MaxDup``

    States violating it are counted and invariant-checked but not expanded —
    TLC CONSTRAINT semantics.
    """
    return (xp.all(struct["term"] <= bounds.max_term)
            & xp.all(struct["logLen"] <= bounds.max_log)
            & (xp.sum((struct["msgCount"] > 0).astype(xp.int32))
               <= bounds.max_msgs)
            & xp.all(struct["msgCount"] <= bounds.max_dup))

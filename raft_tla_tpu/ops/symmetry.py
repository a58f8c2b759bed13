"""Symmetry reduction over the Server model values (TLC SYMMETRY stanza).

The reference binds ``Server`` to model values (``raft.cfg:6``), which TLC
can quotient by permutation symmetry (its classic state-space reduction —
the spec never distinguishes individual servers).  This module implements
the same reduction for the tensor checker, the TPU way:

The dedup key of a state becomes its **orbit-minimal fingerprint**:
``min over all permutations π of fp(canonicalize(π(s)))``, where ``π(s)``
renumbers every server-indexed axis and server-valued field.  The min is
orbit-invariant, so two states equal up to server renaming share one key
and one store row — the reachable count becomes the orbit count, exactly
TLC's SYMMETRY semantics (including its property: the stored witness per
orbit is whichever member was discovered first).  The loop that says what
a key is — permute, canonicalize, pack, fingerprint, |π| times — is
:func:`orbit_fingerprint`, and the host oracle runs it as written.  On
device nothing is permuted (:func:`build_orbit_fp`): the key is linear in
the state's words before its finaliser, so the image's key is taken from
the *unmoved* state against that permutation's row of a host-built table
of permuted constants, and the message bag is ranked where the loop sorts
it — the same bits, and since PR 42 the table is ``int8`` limbs, so the
linear part of a block of images' keys is one matrix product on the MXU.
Since PR 51 that holds for the faithful-mode history under Server
symmetry too (``voterLog`` on the table, ``allLogs`` once a call, the
``elections`` records as packed keys); only a value permutation still
moves data (``logVal``, and the history's log ranks).

Permuting one state under ``p`` (new index of old server j is ``p[j]``):

- per-server axes (role, term, votedFor, commitIndex, logLen, log*,
  vResp, vGrant): the image's row ``p[j]`` is the state's row ``j``;
- server-valued *contents*: ``votedFor`` ids map through ``p`` (0 = Nil
  fixed); vote bitmasks move bit j to bit ``p[j]``;
- ``nextIndex``/``matchIndex`` reorder both axes;
- message records rewrite their ``src``/``dst`` fields through ``p``
  (occupied slots only — empty slots stay all-zero), then the bag
  re-canonicalizes (sort order may change under renaming).

``Value`` symmetry (TLC's ``Permutations(Value)``) composes: values have no
distinguished elements in the spec (they only enter through ``ClientRequest``
and flow inertly through logs and ``mentries``), so the orbit key may also
minimize over value permutations.  Permuting values remaps ``logVal``
contents, the message entry-value field, and — in faithful mode — every
log-universe rank (``ops/loguniv.py``) through a precomputed static
rank-permutation table (allLogs bitmasks permute bitwise).  The full orbit
pass is then ``n! * V!`` static transforms.
"""

from __future__ import annotations

import functools
import itertools
import math

import numpy as np

from raft_tla_tpu.config import Bounds
from raft_tla_tpu.ops import fingerprint as fpr
from raft_tla_tpu.ops import msgbits as mb
from raft_tla_tpu.ops import state as st

MAX_SYM_SERVERS = 6      # 720 permutations; beyond this the orbit pass dwarfs the step


def permutations(bounds: Bounds) -> tuple:
    if bounds.n_servers > MAX_SYM_SERVERS:
        raise ValueError(
            f"Server symmetry supports at most {MAX_SYM_SERVERS} servers "
            f"(got {bounds.n_servers}: {math.factorial(bounds.n_servers)}"
            " permutations)")
    return tuple(itertools.permutations(range(bounds.n_servers)))


MAX_SYM_VALUES = 5       # 120 value permutations


def value_permutations(bounds: Bounds) -> tuple:
    if bounds.n_values > MAX_SYM_VALUES:
        raise ValueError(
            f"Value symmetry supports at most {MAX_SYM_VALUES} values "
            f"(got {bounds.n_values})")
    return tuple(itertools.permutations(range(bounds.n_values)))


@functools.lru_cache(maxsize=None)
def _rank_maps(bounds: Bounds) -> tuple:
    """Per value-permutation q: int32[U] mapping each log rank to the rank
    of the value-permuted log (faithful mode; identity-permutation first)."""
    from raft_tla_tpu.ops.loguniv import LogUniverse
    uni = LogUniverse.of(bounds)
    maps = []
    for q in value_permutations(bounds):
        m = np.empty((uni.size,), np.int32)
        for r in range(uni.size):
            log = uni.tuple_of_id(r)
            m[r] = uni.id_of_tuple(tuple((t, q[v - 1] + 1) for t, v in log))
        maps.append(m)
    return tuple(maps)


def permute_values(struct: dict, qi: int, bounds: Bounds, xp) -> dict:
    """Apply the ``qi``-th value permutation to one state struct.

    Remaps ``logVal`` contents (0 = padding fixed), the message entry-value
    field ``e`` (zero for every non-AppendEntriesRequest record, and the
    LUT fixes 0), and in faithful mode every log rank through the static
    rank table — ``allLogs`` permutes bitwise.
    """
    q = value_permutations(bounds)[qi]
    V = bounds.n_values
    vlut = xp.asarray((0,) + tuple(q[v - 1] + 1 for v in range(1, V + 1)))
    out = dict(struct)
    out["logVal"] = vlut[struct["logVal"]]
    e_sh, e_w = mb._LO_FIELDS["e"]
    lo = struct["msgLo"]
    e_lut = xp.asarray((0,) + tuple(q[v - 1] + 1 for v in range(1, V + 1))
                       + tuple(0 for _ in range((1 << e_w) - V - 1)))
    new_lo = (lo & ~(((1 << e_w) - 1) << e_sh)) \
        | (e_lut[(lo >> e_sh) & ((1 << e_w) - 1)] << e_sh)
    if "allLogs" in struct:
        rmap = xp.asarray(_rank_maps(bounds)[qi])
        U = int(rmap.shape[0])
        rlut1 = xp.concatenate([xp.zeros((1,), xp.int32),
                                rmap.astype(xp.int32) + 1])  # rank+1 form
        out["vLog"] = rlut1[struct["vLog"]]
        out["eLog"] = rmap[struct["eLog"]]
        out["eVLog"] = rlut1[struct["eVLog"]]
        # mlog rank rides the g field of the lo word
        g_sh, g_w = mb._LO_FIELDS["g"]
        g_lut = xp.concatenate(
            [rmap.astype(xp.int32),
             xp.zeros(((1 << g_w) - U,), xp.int32)])
        new_lo = (new_lo & ~(((1 << g_w) - 1) << g_sh)) \
            | (g_lut[(new_lo >> g_sh) & ((1 << g_w) - 1)] << g_sh)
        # allLogs: bit r of the old mask becomes bit rmap[r] of the new
        # one.  Contributions within a word are distinct bit positions, so
        # an integer sum IS the bitwise OR.  Bits 0..30 sum safely in
        # int32; the sign bit is OR'd in separately (no x64 under jit).
        rs = xp.arange(U)
        bits = ((struct["allLogs"][rs // 32] >> (rs % 32)) & 1)
        Wa = struct["allLogs"].shape[0]
        in_word = (rmap[None, :] // 32) == xp.arange(Wa)[:, None]  # [Wa, U]
        tb = rmap[None, :] % 32
        low = xp.where(in_word & (tb < 31) & (bits[None, :] > 0),
                       xp.asarray(1, xp.int32) << tb, 0).sum(axis=1)
        top = (in_word & (tb == 31) & (bits[None, :] > 0)).any(axis=1)
        out["allLogs"] = (low.astype(xp.int32)
                          | xp.where(top, xp.asarray(-2**31, xp.int32), 0))
    occupied = struct["msgCount"] > 0
    out["msgLo"] = xp.where(occupied, new_lo, struct["msgLo"])
    return out


def permute_struct(struct: dict, p: tuple, bounds: Bounds, xp) -> dict:
    """Apply server permutation ``p`` to one state struct (then the caller
    must re-canonicalize the message bag)."""
    n = bounds.n_servers
    inv = tuple(p.index(k) for k in range(n))      # new row k = old row inv[k]
    inv_idx = xp.asarray(inv)
    # votedFor lookup: 0 stays Nil, id j+1 -> p[j]+1
    vf_map = xp.asarray((0,) + tuple(p[j] + 1 for j in range(n)))

    def rows(a):
        return a[inv_idx, ...]

    def bitperm(mask):
        out = xp.zeros_like(mask)
        for j in range(n):
            out = out | (((mask >> j) & 1) << p[j])
        return out

    # src/dst fields of occupied message slots, via the packed hi word
    s_sh, s_w = mb._HI_FIELDS["src"]
    d_sh, d_w = mb._HI_FIELDS["dst"]
    keep = ~(((1 << s_w) - 1) << s_sh | ((1 << d_w) - 1) << d_sh)
    hi = struct["msgHi"]
    occupied = struct["msgCount"] > 0
    p_lut = xp.asarray(p + tuple(0 for _ in range(16 - n)))  # 4-bit fields
    new_hi = (hi & keep) | (p_lut[(hi >> s_sh) & ((1 << s_w) - 1)] << s_sh) \
        | (p_lut[(hi >> d_sh) & ((1 << d_w) - 1)] << d_sh)
    new_hi = xp.where(occupied, new_hi, hi)

    out = {
        "role": rows(struct["role"]),
        "term": rows(struct["term"]),
        "votedFor": vf_map[rows(struct["votedFor"])],
        "commitIndex": rows(struct["commitIndex"]),
        "logLen": rows(struct["logLen"]),
        "logTerm": rows(struct["logTerm"]),
        "logVal": rows(struct["logVal"]),
        "vResp": bitperm(rows(struct["vResp"])),
        "vGrant": bitperm(rows(struct["vGrant"])),
        "nextIndex": struct["nextIndex"][inv_idx, :][:, inv_idx],
        "matchIndex": struct["matchIndex"][inv_idx, :][:, inv_idx],
        "msgHi": new_hi,
        "msgLo": struct["msgLo"],
        "msgCount": struct["msgCount"],
    }
    if "eTerm" in struct:
        # Faithful-mode history (ops/state.py HISTORY_FIELDS).  Log ranks
        # contain no server ids, so allLogs/eLog/mlog are fixed points;
        # voterLog permutes both axes like nextIndex, election records
        # remap eleader/evotes/evoterLog (slot re-sort happens in the
        # caller's canonicalize, like the message bag).
        eocc = struct["eTerm"] > 0
        lead_lut = xp.asarray(p)
        out.update({
            "allLogs": struct["allLogs"],
            "vLog": struct["vLog"][inv_idx, :][:, inv_idx],
            "eTerm": struct["eTerm"],
            "eLeader": xp.where(eocc, lead_lut[struct["eLeader"]],
                                struct["eLeader"]),
            "eLog": struct["eLog"],
            "eVotes": xp.where(eocc, bitperm(struct["eVotes"]),
                               struct["eVotes"]),
            "eVLog": struct["eVLog"][:, inv_idx],
        })
    return out


def _server_luts(bounds: Bounds) -> dict:
    """Stacked lookup tables for every server permutation — the data that
    lets ONE compiled transform apply any group element (build_orbit_fp):
    ``src`` / ``dst`` ``[P, n]``, the message relabel already shifted to
    its field of the hi word, and for the fields the scan still moves
    ``inv [P, n]`` row gathers, ``bit_lut [P, 2^n]`` vote-bitmask
    permutation and ``p_lut [P, n]`` server relabel."""
    ps = permutations(bounds)
    n = bounds.n_servers
    p_lut = np.asarray(ps, np.int32)
    masks = np.arange(1 << n, dtype=np.int32)
    bit_lut = np.zeros((len(ps), 1 << n), np.int32)
    for j in range(n):
        bit_lut |= ((masks >> j) & 1)[None, :] << p_lut[:, j:j + 1]
    return {"src": p_lut << mb.HI_FIELDS["src"][0],
            "dst": p_lut << mb.HI_FIELDS["dst"][0],
            "inv": np.argsort(p_lut, axis=1).astype(np.int32),
            "bit_lut": bit_lut, "p_lut": p_lut}


def _value_luts(bounds: Bounds, faithful: bool) -> dict:
    """Stacked lookup tables per value permutation (build_orbit_fp):
    ``vlut [Q, V+1]`` logVal relabel, ``e_lut [Q, 2^e_w]`` message
    entry-value field, and in faithful mode the log-rank maps."""
    qs = value_permutations(bounds)
    V = bounds.n_values
    e_sh, e_w = mb._LO_FIELDS["e"]
    vlut = np.zeros((len(qs), V + 1), np.int32)
    e_lut = np.zeros((len(qs), 1 << e_w), np.int32)
    for i, q in enumerate(qs):
        vlut[i] = (0,) + tuple(q[v - 1] + 1 for v in range(1, V + 1))
        e_lut[i, :V + 1] = vlut[i]
    out = {"vlut": vlut, "e_lut": e_lut}
    if faithful:
        rmaps = np.stack(_rank_maps(bounds))             # [Q, U]
        U = rmaps.shape[1]
        g_sh, g_w = mb._LO_FIELDS["g"]
        out["rmap"] = rmaps
        out["rlut1"] = np.concatenate(
            [np.zeros((len(qs), 1), np.int32), rmaps + 1], axis=1)
        out["g_lut"] = np.concatenate(
            [rmaps, np.zeros((len(qs), (1 << g_w) - U), np.int32)], axis=1)
    return out


# ---------------------------------------------------------------------------
# The orbit scan's key of one image, taken without moving the state
# (build_orbit_fp).  Before its finaliser the key is linear in the folded
# words (ops/fingerprint), and a server permutation of server-indexed data
# under a fixed linear form is the inverse permutation of the form's
# constants: the fields below give, once a call, a vector of features that
# no permutation changes, and every image's share of the key is its dot
# product with that permutation's row of a host-built table.
# ---------------------------------------------------------------------------

_BAG = ("msgHi", "msgLo", "msgCount")
_ELECTIONS = ("eTerm", "eLeader", "eLog", "eVotes", "eVLog")
# fields a server permutation only moves -> how many of their leading axes
# are server-indexed; a feature is the word itself (``vLog``: log ranks
# hold no server id, so ``voterLog`` is ``nextIndex``' case)
_MOVED_AXES = {"role": 1, "term": 1, "commitIndex": 1, "logLen": 1,
               "logTerm": 1, "logVal": 1, "nextIndex": 2, "matchIndex": 2,
               "vLog": 2}
# fields whose words also name servers: votedFor by id (one feature a
# value, its one-hot), the vote masks by bit (one feature a bit)
_VOTE_MASKS = ("vResp", "vGrant")
_RELABELLED = ("votedFor",) + _VOTE_MASKS


def _linear_fields(axes: tuple, history: bool = False) -> tuple:
    """The fields whose share of an image's key is ``features . table``:
    every per-server parity field and, in faithful mode, ``vLog`` — less
    what a value permutation relabels the contents of, ``logVal`` and the
    ``vLog`` ranks (they keep the data-moving path; the bag and the
    ``elections`` records are ranked, :func:`_bag_sums`,
    :func:`_election_sums`)."""
    return tuple(f for f in st.STATE_FIELDS + ("vLog",) * history
                 if (f in _MOVED_AXES or f in _RELABELLED)
                 and not (f in ("logVal", "vLog") and "Value" in axes))


def scan_forms(bounds: Bounds, axes: tuple) -> dict:
    """Which form each field's share of an image's key takes in
    :func:`build_orbit_fp`, from the SYMMETRY axes and the layout alone:
    ``table`` (features times permuted constants), ``ranked`` (relabelled
    and put in order where it lies: the bag's slots counted into place,
    the ``elections`` records as packed keys), ``once`` (a fixed point of
    the group: summed once a call) or ``moved`` (permuted and
    canonicalised an image at a time).  Every field of the layout is in
    exactly one."""
    lay = st.Layout.of(bounds)
    table = _linear_fields(axes, lay.history)
    still = lay.history and "Value" not in axes
    forms = {"table": table,
             "ranked": _BAG + (_ELECTIONS if still else ()),
             "once": ("allLogs",) if still else ()}
    taken = {f for fs in forms.values() for f in fs}
    forms["moved"] = tuple(f for f in lay.fields if f not in taken)
    return forms


# A feature is a signed byte.  A word that can pass 127 (``vLog`` where
# the log universe has more ranks) is two base-128 digits, two features
# with the constants ``c`` and ``c << 7``: the fold is the identity under
# 2^16, so the word's share of the sum is still linear in its digits.
_DIGIT_BITS = 7


def _key_features(struct: dict, fields: tuple, xp, wide: tuple = ()):
    """``struct[N, ...] -> int8[F, N]``: the permutation-independent
    features of ``fields``, in :func:`_key_table`'s order, lanes minor (a
    ``[N, small]`` array is kept padded to 128 lanes on the TPU).  Every
    entry is below 2^7 — terms, indices, values and server ids are capped
    at 63 by ``config.Bounds`` (:func:`_feature_cap`), the rest are bits —
    so the fold (``x ^ (x >> 16)``) is the identity on all of them and a
    signed byte holds each: the matrix the MXU multiplies by a block of
    permutations' limbs (:func:`_limb_sums`), read once a block.  A field
    of ``wide`` (:func:`_wide_fields`) gives its low digits, then its high
    ones."""
    n = struct["role"].shape[1]
    N = struct["role"].shape[0]
    parts = []
    for f in fields:
        a = struct[f]
        if f == "votedFor":
            a = a[:, :, None] == xp.arange(1, n + 1)      # [N, j, id - 1]
        elif f in _VOTE_MASKS:
            a = (a[:, :, None] >> xp.arange(n)) & 1       # [N, j, bit]
        a = xp.reshape(a, (N, -1))
        if f in wide:
            a = xp.concatenate([a & ((1 << _DIGIT_BITS) - 1),
                                a >> _DIGIT_BITS], axis=1)
        parts.append(a.astype(xp.int8))     # 7-bit: _feature_cap
    return xp.concatenate(parts, axis=1).T


def _key_table(bounds: Bounds, consts, fields: tuple,
               perms: tuple) -> np.ndarray:
    """``uint32[len(perms), 2, F]``: for each server permutation ``p`` the
    two lanes' constants of :func:`_key_features`' entries, such that
    ``features . table[p]`` (mod 2^32) is what ``fields`` add to the sum
    before the finaliser of ``fingerprint(pack(permute_struct(s, p)))``.
    With ``c`` a field's slice of the lane constants (``state.pack``'s
    order) and the image's row ``p[j]`` holding old row ``j``:

    - moved only: ``c[p[j]]`` (``c[p[i], p[j]]`` for the index matrices);
    - ``votedFor`` = id: ``c[p[j]] * (p[id - 1] + 1)`` — the relabelled
      id, which the fold leaves alone;
    - vote masks, bit ``b``: ``c[p[j]] << p[b]`` — distinct bits, so the
      relabelled mask is their sum;
    - a wide field (:func:`_wide_fields`): ``c`` for its low digits, then
      ``c << 7`` for its high ones.
    """
    fc = fpr.field_constants(st.Layout.of(bounds).shapes, consts)
    wide = _wide_fields(bounds, fields)
    table = []
    for p in perms:
        p = np.asarray(p)
        row = []
        for f in fields:
            cf = fc[f][:, p]
            if _MOVED_AXES.get(f) == 2:
                cf = cf[:, :, p]
            elif f == "votedFor":
                cf = cf[:, :, None] * (p + 1).astype(np.uint32)
            elif f in _VOTE_MASKS:
                cf = cf[:, :, None] << p.astype(np.uint32)
            cf = cf.reshape(2, -1)
            if f in wide:
                cf = np.concatenate([cf, cf << np.uint32(_DIGIT_BITS)],
                                    axis=1)
            row.append(cf)
        table.append(np.concatenate(row, axis=1))
    return np.stack(table)


def _linear_sums(phi, row, xp):
    """``phi int8[F, N]`` (:func:`_key_features`) times one permutation's
    ``row uint32[2, F]`` of :func:`_key_table` -> the lanes' two sums.
    The plain statement of the algebra, which the tests hold the limb
    form to; the device takes the sums from :func:`_limb_sums`."""
    with np.errstate(over="ignore"):
        w = phi.astype(xp.uint32)
        return (xp.sum(w * row[0][:, None], axis=0, dtype=xp.uint32),
                xp.sum(w * row[1][:, None], axis=0, dtype=xp.uint32))


# The same sums on the MXU.  A uint32 constant is four balanced base-256
# digits, ``c = sum_l d_l * 2^(8l)  (mod 2^32)`` with every ``d_l`` in
# [-128, 127]: an ``int8`` matrix.  ``sum_f phi_f * d_(f,l)`` is then an
# int8 x int8 product accumulated in int32 — exact while ``F * cap * 128``
# stays under 2^31 — and the four partial sums, shifted to their digit and
# added in uint32, wrap to the word the multiply-reduce gives.  (Each
# lane's sum is built from its own slices of the product, so that the
# compiler fuses the shifts into the fusion that ranks the bag.)
_N_LIMBS = 4
_LIMB_MAX = 128           # |balanced digit| <= 128
# A block is at most eight server permutations: their images lie side by
# side as ``[P_b, N]``, one vector register's sublanes, and the 64-row
# product is what the v5e multiplies cheapest (PERF.md, PR 42: 15 us a
# block at 155,648 lanes, 180 us with 160 rows).  The block's int32
# product ``[P_b * 2 * _N_LIMBS, N]`` may take this much of the device's
# memory besides (full5's dense step: 344,064 lanes, 88 MB).
_BLOCK_PERMS = 8
_PRODUCT_BYTES = 96 << 20


def _word_caps(bounds: Bounds, fields: tuple) -> dict:
    """field -> the largest word it can hold under ``bounds``
    (capacities, one past each constraint; ``vLog`` a log rank + 1)."""
    cap = {"role": 2, "term": bounds.term_cap, "logTerm": bounds.term_cap,
           "commitIndex": bounds.log_cap, "logLen": bounds.log_cap,
           "matchIndex": bounds.log_cap, "nextIndex": bounds.log_cap + 1,
           "logVal": bounds.n_values}
    if "vLog" in fields:
        from raft_tla_tpu.ops.loguniv import LogUniverse
        cap["vLog"] = LogUniverse.of(bounds).size
    return {f: cap.get(f, 1) for f in fields}    # one-hots and bits: 1


def _wide_fields(bounds: Bounds, fields: tuple) -> tuple:
    """The fields of ``fields`` whose words can pass a signed byte: each
    is keyed as two base-128 digits (``_DIGIT_BITS``)."""
    return tuple(f for f, cap in _word_caps(bounds, fields).items()
                 if cap >= 1 << _DIGIT_BITS)


def _feature_cap(bounds: Bounds, fields: tuple) -> int:
    """The largest entry :func:`_key_features` can hold for ``fields``
    under ``bounds``: a word, or a wide word's digit."""
    return max(min(cap, (1 << _DIGIT_BITS) - 1)
               for cap in _word_caps(bounds, fields).values())


def _check_limb_range(n_features: int, feature_cap: int) -> None:
    """Refuse a layout whose limb product would not be exact: a feature
    an ``int8`` cannot hold, or a sum that could leave ``int32``."""
    if feature_cap > 127:
        raise ValueError(
            f"orbit key features must fit int8 (cap {feature_cap} > 127)")
    if n_features * feature_cap * _LIMB_MAX >= 1 << 31:
        raise ValueError(
            f"orbit key limb sums could overflow int32: {n_features} "
            f"features x cap {feature_cap} x {_LIMB_MAX} >= 2^31")


def _key_limbs(table: np.ndarray) -> np.ndarray:
    """:func:`_key_table`'s ``uint32[P, 2, F]`` as balanced base-256
    digits, ``int8[P, 2, _N_LIMBS, F]``, least significant first.  The
    carry out of the top digit is a multiple of 2^32 and is dropped."""
    c = table.astype(np.int64)
    limbs = []
    for _ in range(_N_LIMBS):
        d = ((c + 128) & 0xFF) - 128
        limbs.append(d)
        c = (c - d) >> 8
    return np.stack(limbs, axis=2).astype(np.int8)   # 8-bit: in [-128, 127]


def _limb_sums(limbs, phi, xp):
    """``limbs int8[P_b, 2, _N_LIMBS, F]`` (:func:`_key_limbs`) times
    ``phi int8[F, N]`` -> ``uint32[P_b, 2, N]``, bit for bit what
    :func:`_linear_sums` gives for each of the block's permutations: one
    matrix product in int32, then the digits shifted home and added."""
    rows = limbs.reshape((-1, limbs.shape[-1]))
    if xp is np:
        d = rows.astype(np.int32) @ phi.astype(np.int32)
    else:
        import jax
        d = jax.lax.dot_general(rows, phi, (((1,), (0,)), ((), ())),
                                preferred_element_type=xp.int32)
    d = d.reshape(limbs.shape[:-1] + (phi.shape[1],))
    sums = []
    for lane in range(2):
        s = d[:, lane, 0].astype(xp.uint32)
        for digit in range(1, _N_LIMBS):
            s = s + (d[:, lane, digit].astype(xp.uint32)
                     << xp.uint32(8 * digit))
        sums.append(s)
    return xp.stack(sums, axis=1)


def _block_perms(n_perms: int, n_lanes: int) -> int:
    """How many server permutations one product takes: the largest
    divisor of ``n_perms`` that is at most ``_BLOCK_PERMS`` and whose
    int32 product stays within ``_PRODUCT_BYTES`` (one permutation where
    none does)."""
    per_perm = 2 * _N_LIMBS * 4 * max(1, n_lanes)
    fit = min(_BLOCK_PERMS, max(1, _PRODUCT_BYTES // per_perm))
    return max(d for d in range(1, fit + 1) if n_perms % d == 0)


def _lex_min(a, b, xp=None):
    """The lesser of two ``(hi, lo)`` keys, lane by lane (``a`` may be
    ``None``: nothing yet)."""
    if a is None:
        return b
    if xp is None:
        import jax.numpy as xp
    (ah, al), (bh, bl) = a, b
    take = (bh < ah) | ((bh == ah) & (bl < al))
    return xp.where(take, bh, ah), xp.where(take, bl, al)


def _least_image(best, hi, lo):
    """``best`` against the least of a block's images' keys
    (``hi, lo: uint32[P_b, N]``, side by side)."""
    import jax
    import jax.numpy as jnp
    top = jnp.uint32(0xFFFFFFFF)
    return _lex_min(best, jax.lax.reduce((hi, lo), (top, top), _lex_min,
                                         (0,)))


def _relabel_hi(hi, src_row, dst_row, xp):
    """Message hi words with ``src`` / ``dst`` mapped through one server
    permutation, given as its rows of ``_server_luts``' ``src`` / ``dst``
    (``p[j]`` shifted to the field).  Same bits as ``permute_struct`` on
    an occupied slot; an empty slot's word is not kept (the caller drops
    it)."""
    (s_sh, s_w), (d_sh, d_w) = mb.HI_FIELDS["src"], mb.HI_FIELDS["dst"]
    s_mask, d_mask = (1 << s_w) - 1, (1 << d_w) - 1
    src, dst = (hi >> s_sh) & s_mask, (hi >> d_sh) & d_mask
    out = hi & ~(s_mask << s_sh | d_mask << d_sh)
    for j in range(src_row.shape[0]):
        out = out | xp.where(src == j, src_row[j], 0) \
            | xp.where(dst == j, dst_row[j], 0)
    return out


def _relabel_lo(lo, luts: dict, xp):
    """Message lo words with the entry value (field ``e``) and, in
    faithful mode, the ``mlog`` rank (field ``g``) mapped through one
    value permutation, given as its rows of :func:`_value_luts`.  Same
    bits as ``permute_values`` on an occupied slot; an empty slot's word
    is not kept (the caller drops it)."""
    e_sh, e_w = mb.LO_FIELDS["e"]
    lo = (lo & ~(((1 << e_w) - 1) << e_sh)) \
        | (luts["e_lut"][(lo >> e_sh) & ((1 << e_w) - 1)] << e_sh)
    if "g_lut" in luts:
        g_sh, g_w = mb.LO_FIELDS["g"]
        lo = (lo & ~(((1 << g_w) - 1) << g_sh)) \
            | (luts["g_lut"][(lo >> g_sh) & ((1 << g_w) - 1)] << g_sh)
    return lo


def _bag_sums(hi, lo, ct, cbag, xp):
    """What the message bag adds to the lanes' sums before the finaliser,
    **ranked, not sorted**.  ``hi``, ``lo``, ``ct``: a slot a sequence
    element, each ``int32[N]``: the words of an image's slots before
    ``state.canonicalize`` would sort them (every slot a vector over the
    lanes of its own: all of this is elementwise and fuses); ``cbag``:
    ``uint32[2, 3, S]``, the lane constants of ``msgHi`` / ``msgLo`` /
    ``msgCount``.  ``canonicalize`` zeroes an empty slot, which then adds
    0 wherever it lands, and sorts the occupied ones first by (hi, lo):
    an occupied slot lands at its rank among the occupied, and takes that
    position's constants.  The comparison is ``_network_sort``'s own
    (``<=`` on int32, the earlier slot first on a tie), so the rank is the
    network's position even for slots it could not tell apart."""
    S = len(hi)
    occ = [c > 0 for c in ct]
    rank = [0] * S
    for s in range(S):
        for t in range(s + 1, S):
            le = (hi[s] < hi[t]) | ((hi[s] == hi[t]) & (lo[s] <= lo[t]))
            rank[t] = rank[t] + (occ[s] & le).astype(xp.int32)
            rank[s] = rank[s] + (occ[t] & ~le).astype(xp.int32)
    s1 = s2 = xp.uint32(0)
    with np.errstate(over="ignore"):
        for s in range(S):
            at = [rank[s] == r for r in range(S - 1)]
            t1 = t2 = xp.uint32(0)
            for a, c in zip((hi, lo, ct), np.moveaxis(cbag, 1, 0)):
                c1, c2 = c[0, S - 1], c[1, S - 1]
                for r, here in enumerate(at):
                    c1 = xp.where(here, c[0, r], c1)
                    c2 = xp.where(here, c[1, r], c2)
                w = fpr.fold(a[s], xp)
                t1, t2 = t1 + w * c1, t2 + w * c2
            s1 = s1 + xp.where(occ[s], t1, xp.uint32(0))
            s2 = s2 + xp.where(occ[s], t2, xp.uint32(0))
    return s1, s2


# The ``elections`` records of faithful mode, put in order as the bag is:
# with no state moved.  A record's sort key is ``canonicalize_elections``'
# own — empty last, then ``eTerm``, ``eLeader``, ``eLog``, ``eVotes``, the
# ``eVLog`` columns — packed most significant first into non-negative
# ``int32`` words, so one compare a word orders two records.  Every part
# of it is a small bit field and the words hold all of a record: two
# records with one key are one record, the keys in order are the sorted
# image's records, and each word of a record is cut from its key.
_KEY_WORD_BITS = 31


def _election_key_plan(bounds: Bounds) -> tuple:
    """``(parts, n_words)``: a record's key parts in sort order, each
    ``(field, column or None, word, shift, width)``, packed greedily into
    ``n_words`` words of ``_KEY_WORD_BITS`` bits.  ``eTerm`` is keyed as
    ``(eTerm - 1) mod 2^width``: the order of the terms, and an empty
    slot's 0 the greatest (``width`` holds ``term_cap`` and one more);
    the other widths hold the words' capacities (a server id, a rank of
    the log universe, a vote mask, a rank + 1)."""
    from raft_tla_tpu.ops.loguniv import LogUniverse
    n, U = bounds.n_servers, LogUniverse.of(bounds).size
    widths = [("eTerm", None, bounds.term_cap.bit_length()),
              ("eLeader", None, max(1, (n - 1).bit_length())),
              ("eLog", None, max(1, (U - 1).bit_length())),
              ("eVotes", None, n)] \
        + [("eVLog", j, U.bit_length()) for j in range(n)]
    parts, word, free = [], 0, _KEY_WORD_BITS
    for f, col, width in widths:
        if width > free:
            word, free = word + 1, _KEY_WORD_BITS
        free -= width
        parts.append((f, col, word, free, width))
    return tuple(parts), word + 1


def _key_places(plan: tuple) -> dict:
    """``(field, column or None) -> (word, shift, width)`` of a plan."""
    return {(f, col): place for f, col, *place in plan[0]}


def _election_luts(bounds: Bounds, plan: tuple) -> dict:
    """What relabels a record's key under each server permutation, with no
    gather: ``e_lead [P]`` the permutation as one word, ``p[j]`` in the
    key's ``eLeader`` width at bit ``j * width``; ``e_vote [P, n]`` where
    vote bit ``j`` goes in its key word, ``p[j]`` past the field's shift;
    ``e_vsh`` / ``e_vword [P, n]`` the shift and the key word of the
    place ``eVLog`` column ``j`` goes to, column ``p[j]``'s."""
    at = _key_places(plan)
    p = np.asarray(permutations(bounds), np.int32)         # [P, n]
    n = p.shape[1]
    lead_w = at["eLeader", None][2]
    col_word, col_sh, _ = (np.asarray(a, np.int32) for a in zip(
        *(at["eVLog", j] for j in range(n))))
    return {"e_lead": (p << (lead_w * np.arange(n, dtype=np.int32)))
            .sum(axis=1, dtype=np.int32),
            "e_vote": p + np.int32(at["eVotes", None][1]),
            "e_vsh": col_sh[p], "e_vword": col_word[p]}


def _election_records(struct: dict, plan: tuple, xp) -> list:
    """The ``elections`` slots slot-major, once a call: for each slot the
    ``[N]`` vectors (lanes minor) that no permutation changes —
    ``occ``; ``fixed``, a key word a list entry with ``eTerm`` and ``eLog``
    in place; ``stale``, ``eLeader`` and ``eVotes`` in place as they
    stand (``permute_struct`` relabels neither in an empty slot); and
    what a permutation relabels: ``lead_at``, the leader's bit in
    ``e_lead``, ``votes``, and ``vlog``, a column a list entry."""
    n_words, at = plan[1], _key_places(plan)
    E, n = struct["eVLog"].shape[1:]
    recs = []
    for e in range(E):
        term, lead, log, votes = (struct[f][:, e] for f in _ELECTIONS[:4])
        fixed = [xp.zeros_like(term) for _ in range(n_words)]
        stale = list(fixed)
        w, sh, width = at["eTerm", None]
        fixed[w] = fixed[w] | (((term - 1) & ((1 << width) - 1)) << sh)
        w, sh, _ = at["eLog", None]
        fixed[w] = fixed[w] | (log << sh)
        w, sh, lead_w = at["eLeader", None]
        stale[w] = stale[w] | (lead << sh)
        w, sh, _ = at["eVotes", None]
        stale[w] = stale[w] | (votes << sh)
        recs.append({"occ": term > 0, "fixed": fixed, "stale": stale,
                     "lead_at": lead * lead_w, "votes": votes,
                     "vlog": [struct["eVLog"][:, e, j] for j in range(n)]})
    return recs


def _election_sums(recs: list, luts: dict, plan: tuple, celect: dict, xp):
    """What the ``elections`` records of one image add to the lanes' sums
    before the finaliser, **relabelled and ordered where they lie, not
    permuted and sorted as arrays**.  ``recs``:
    :func:`_election_records`; ``luts``: one permutation's rows of
    :func:`_election_luts`; ``celect``: the lane constants of the five
    fields (``fingerprint.field_constants``).

    A slot's key under the permutation is put together by shifts
    (``eLeader`` and ``eVotes`` through the permutation where the slot is
    occupied, the ``eVLog`` columns always: ``permute_struct``'s bits).
    The keys then go through ``canonicalize_elections``' own comparator
    network, a compare and two selects a key word: a key is all of its
    record, so whatever the network does on a tie the keys come out as
    the sorted image's records, position by position, and each word of a
    record is cut from its key and takes the constants of its position.
    The fold is the identity on every such word (under 2^11).  An empty
    slot is keyed like any other: all-zero it adds 0 wherever it lands,
    and with stale words it adds what the loop's sort would make it
    add."""
    parts, n_words = plan
    at = _key_places(plan)
    (w_lead, sh_lead, lead_w), (w_votes, _, _) = \
        at["eLeader", None], at["eVotes", None]
    n = len(recs[0]["vlog"])
    w_vlog = sorted({at["eVLog", j][0] for j in range(n)})
    keys = []
    for r in recs:
        relab = [0] * n_words
        relab[w_lead] = ((luts["e_lead"] >> r["lead_at"])
                         & ((1 << lead_w) - 1)) << sh_lead
        for j in range(n):
            relab[w_votes] = relab[w_votes] \
                + (((r["votes"] >> j) & 1) << luts["e_vote"][j])
        key = list(r["fixed"])
        for w in sorted({w_lead, w_votes}):
            key[w] = key[w] + xp.where(r["occ"], relab[w], r["stale"][w])
        for j in range(n):
            col = r["vlog"][j] << luts["e_vsh"][j]
            for w in w_vlog:
                key[w] = key[w] + (col if len(w_vlog) == 1 else xp.where(
                    luts["e_vword"][j] == w, col, 0))
        keys.append(key)
    for i, j in st._oddeven_pairs(len(keys)):
        a, b = keys[i], keys[j]
        gt = a[-1] > b[-1]
        for x, y in zip(a[-2::-1], b[-2::-1]):
            gt = (x > y) | ((x == y) & gt)
        keys[i] = [xp.where(gt, y, x) for x, y in zip(a, b)]
        keys[j] = [xp.where(gt, x, y) for x, y in zip(a, b)]
    s1 = s2 = xp.uint32(0)
    with np.errstate(over="ignore"):
        for r, key in enumerate(keys):
            for f, col, w, sh, width in parts:
                v = (key[w] >> sh) & ((1 << width) - 1)
                if f == "eTerm":
                    v = (v + 1) & ((1 << width) - 1)
                c = celect[f][:, r] if col is None else celect[f][:, r, col]
                v = v.astype(xp.uint32)
                s1, s2 = s1 + v * c[0], s2 + v * c[1]
    return s1, s2


def _permute_struct_batch(struct: dict, fields: tuple, luts: dict, xp):
    """The images of ``fields`` under one server permutation, over a
    leading batch axis, the permutation given as its traced rows of
    :func:`_server_luts` (``permute_struct``'s arithmetic, same bits).
    Only what the scan's linear key does not cover comes through here:
    ``logVal`` on its way to a value relabel, and the faithful-mode
    history, where log ranks hold no server ids (``allLogs``, ``eTerm``,
    ``eLog`` are fixed points)."""
    inv, bit_lut, p_lut = luts["inv"], luts["bit_lut"], luts["p_lut"]

    def rows(a):
        return xp.take(a, inv, axis=1)

    image = {
        "logVal": lambda: rows(struct["logVal"]),
        "vLog": lambda: xp.take(rows(struct["vLog"]), inv, axis=2),
        "eLeader": lambda: xp.where(struct["eTerm"] > 0,
                                    p_lut[struct["eLeader"]],
                                    struct["eLeader"]),
        "eVotes": lambda: xp.where(struct["eTerm"] > 0,
                                   bit_lut[struct["eVotes"]],
                                   struct["eVotes"]),
        "eVLog": lambda: xp.take(struct["eVLog"], inv, axis=2),
    }
    return {f: image[f]() if f in image else struct[f] for f in fields}


def _permute_values_batch(struct: dict, fields: tuple, luts: dict, xp):
    """The images of ``fields`` under one value permutation, over a
    leading batch axis, the permutation given as its traced rows of
    :func:`_value_luts` (``permute_values``' arithmetic, same bits).
    Fields a value permutation leaves alone pass through."""
    def all_logs():
        # bit r of the old mask becomes bit rmap[r] of the new one (the
        # sum-as-OR trick of permute_values; the sign bit is OR'd in
        # separately — no x64 under jit)
        rmap = luts["rmap"]
        U = int(rmap.shape[0])
        rs = np.arange(U)
        Wa = struct["allLogs"].shape[1]
        bits = (struct["allLogs"][:, rs // 32] >> (rs % 32)) & 1   # [N, U]
        in_word = (rmap[None, :] // 32) == xp.arange(Wa)[:, None]  # [Wa, U]
        tb = rmap % 32                                             # [U]
        low = xp.where(
            in_word[None] & (tb < 31)[None, None] & (bits[:, None, :] > 0),
            xp.asarray(1, xp.int32) << tb, 0).sum(axis=2)
        top = (in_word[None] & (tb == 31)[None, None]
               & (bits[:, None, :] > 0)).any(axis=2)
        return (low.astype(xp.int32)
                | xp.where(top, xp.asarray(-2**31, xp.int32), 0))

    image = {
        "logVal": lambda: luts["vlut"][struct["logVal"]],
        "allLogs": all_logs,
        "vLog": lambda: luts["rlut1"][struct["vLog"]],
        "eLog": lambda: luts["rmap"][struct["eLog"]],
        "eVLog": lambda: luts["rlut1"][struct["eVLog"]],
    }
    return {f: image[f]() if f in image else struct[f] for f in fields}


def build_orbit_fp(bounds: Bounds, axes: tuple, consts, faithful: bool):
    """Batched orbit-minimal fingerprints: ``struct[N, ...] -> (hi, lo)[N]``.

    Bit-identical to :func:`orbit_fingerprint` (the loop that permutes,
    canonicalises, packs and fingerprints each image; the (hi, lo)
    lexicographic min is order-independent), compiled as ONE body
    iterated by ``lax.scan`` over **blocks of ``P_b`` server
    permutations** (:func:`_block_perms`: eight, or the largest divisor
    of P under it, from the shapes alone) — and the body **moves no
    state data**.  The sum before the finaliser is a sum of
    per-field sums (``fingerprint.field_sums``), and each field takes the
    form its image allows, chosen here, statically, from ``axes`` and
    ``faithful``:

    - the per-server fields (:func:`_linear_fields`): one ``int8`` matrix
      of features built **once a call, outside the scan**
      (:func:`_key_features`), times the block's rows of a host-built
      table of permuted constants (:func:`_key_table`) split into
      ``int8`` limbs (:func:`_key_limbs`): **one** ``dot_general`` a
      block on the MXU, ``[P_b * 8, F] x [F, N]`` in int32, the limbs
      shifted home and added in uint32 (:func:`_limb_sums`).  No gather,
      no relabel, no multiply-reduce an image; under Value symmetry once
      a server permutation, not once a (p, q);
    - the message bag: ``src`` / ``dst`` relabelled (the fold is not
      linear in them), then ranked, not sorted (:func:`_bag_sums`), the
      block's images side by side (``[P_b, N]``, one fusion with the
      finaliser and the block's least key);
    - the faithful-mode history under Server symmetry alone (PR 51), none
      of it moved: ``vLog`` rides the key table (log ranks hold no server
      id: ``nextIndex``' case, a rank past 127 as two base-128 digits);
      ``allLogs`` is a fixed point of every server permutation and is
      summed once a call; the ``elections`` records are relabelled into
      packed keys and ordered as keys (:func:`_election_sums`), side by
      side with the bag in the same images' body;
    - what is still moved, permuted and canonicalised as the loop does,
      for those fields alone, an image at a time (``moved_sums``):
      ``logVal`` under Value symmetry (a value permutation relabels its
      contents, which a table of permuted constants cannot: it would
      take a one-hot a value), and all of the history wherever a value
      permutation is in the group (every log rank maps through
      :func:`_rank_maps`, ``allLogs`` bitwise).

    Which field took which form is on the returned function
    (``orbit_fp.forms``, :func:`scan_forms`), and the engines put the
    count of the moved ones on their ``pass`` span
    (``scan_moved_fields``).

    The round-1 unrolled graph at five servers (120 copies) crashed
    compiles at chunk 2048 and capped the elect5 run at ~3k orbits/s;
    the scan keeps the program size constant in |G|.  What an image
    costs on the chip (PERF.md section 6, PR 42): 60 us at full5's
    344,064 lanes and 23 us at elect5's 155,648 (0.15-0.18 ns a lane: of
    a block of eight, the product 78 us, the images' fusion 193 us, what
    the compiler recomputes a block 80 us, at the wider); while each
    image had its own multiply-reduce on the vector unit, 244 and 81 us
    (PR 29-41); while the body regathered, relabelled and re-sorted the
    state 120 times a step: PERF.md, PR 29.
    """
    import jax
    import jax.numpy as jnp

    from raft_tla_tpu.ops.kernels import ORBIT_MOVED_SCOPE

    server, value = "Server" in axes, "Value" in axes
    lay = st.Layout.of(bounds)
    n = lay.n
    perms = permutations(bounds) if server else (tuple(range(n)),)
    P = len(perms)
    forms = scan_forms(bounds, axes)
    linear, moved = forms["table"], forms["moved"]
    ranked = _ELECTIONS[0] in forms["ranked"]     # the records, not moved
    wide = _wide_fields(bounds, linear)
    table = _key_table(bounds, consts, linear, perms)
    _check_limb_range(table.shape[-1], _feature_cap(bounds, linear))
    limbs = jnp.asarray(_key_limbs(table))               # [P, 2, 4, F]
    sluts = _server_luts(bounds) if server else None
    fc = fpr.field_constants(lay.shapes, consts)
    cbag = np.stack([fc[f] for f in _BAG], axis=1)       # [2, 3, S]
    if ranked:
        plan = _election_key_plan(bounds)
        sluts = {**(sluts or {}), **_election_luts(bounds, plan)}
    sluts = sluts and {k: jnp.asarray(v) for k, v in sluts.items()}
    vluts = {k: jnp.asarray(v)
             for k, v in _value_luts(bounds, faithful).items()} \
        if value else None

    def orbit_fp(struct):
        # what no group element changes, once a call: the features of
        # the linear fields, the bag slot-major, a vector a slot, and in
        # faithful mode the election records likewise and allLogs' sums
        phi = _key_features(struct, linear, jnp, wide)
        hi0, lo0, ct = ([struct[f][:, s] for s in range(lay.S)]
                        for f in _BAG)
        Pb = _block_perms(P, phi.shape[1])
        if ranked:
            with jax.named_scope(ORBIT_MOVED_SCOPE):
                recs = _election_records(struct, plan, jnp)
                once = fpr.field_sums(struct, consts, jnp, forms["once"])

        def image(s1, s2, sl, vl):
            # one image's key from its two sums over everything but the
            # bag, and its rows of the lookup tables
            hi, lo = hi0, lo0
            if server:
                hi = [_relabel_hi(w, sl["src"], sl["dst"], jnp) for w in hi]
            if value:
                lo = [_relabel_lo(w, vl, jnp) for w in lo]
            b1, b2 = _bag_sums(hi, lo, ct, cbag, jnp)
            if ranked:
                # what the history adds to the image's key (a scope
                # inside the caller's ``orbit_scan``: NESTED_SCOPES),
                # kept a fusion of its own: fused into the images' one
                # it ran 0.54 ms a step slower on the v5e and read as
                # ``orbit_scan`` alone (PERF.md section 6, PR 51)
                with jax.named_scope(ORBIT_MOVED_SCOPE):
                    e1, e2 = _election_sums(recs, sl, plan, fc, jnp)
                    e1, e2 = jax.lax.optimization_barrier(
                        (e1 + once[0], e2 + once[1]))
                b1, b2 = b1 + e1, b2 + e2
            return fpr.finalise(s1 + b1, s2 + b2, jnp)

        def moved_sums(sl, vl):
            # what the fields that have to move add to one image's sums
            # (under the same scope)
            with jax.named_scope(ORBIT_MOVED_SCOPE):
                t = struct
                if server:
                    t = {**t, **_permute_struct_batch(t, moved, sl, jnp)}
                if value:
                    t = {**t, **_permute_values_batch(t, moved, vl, jnp)}
                if faithful:   # the elections sort alone: the bag is ranked
                    t = {**t, **jax.vmap(
                        lambda s: st.canonicalize_elections(s, jnp))(t)}
                return fpr.field_sums(t, consts, jnp, moved)

        def block(best, xs):
            # the linear sums of P_b permutations in one product; their
            # images side by side, [P_b, N]; the least of them
            block_limbs, sl = xs
            sums = _limb_sums(block_limbs, phi, jnp)     # [P_b, 2, N]

            def under(best, vl):
                s1, s2 = sums[:, 0], sums[:, 1]
                if moved:    # an image at a time: they hold [N, n, L] arrays
                    m1, m2 = jax.lax.map(lambda r: moved_sums(r, vl), sl) \
                        if server else moved_sums(None, vl)
                    s1, s2 = s1 + m1, s2 + m2
                hi, lo = jax.vmap(image, in_axes=(0, 0, 0, None))(
                    s1, s2, sl, vl)
                return _least_image(best, hi, lo), None

            if value:
                return jax.lax.scan(under, best, vluts)
            return under(best, None)

        # derive the +inf init from the input so it inherits the input's
        # varying manual axes — a constant-built carry breaks the scan
        # type match when this runs inside shard_map (CP lane sharding)
        top = jnp.zeros_like(struct["role"][:, 0]).astype(jnp.uint32) \
            | jnp.uint32(0xFFFFFFFF)
        blocks = jax.tree.map(
            lambda a: a.reshape((P // Pb, Pb) + a.shape[1:]),
            (limbs, sluts))
        (bh, bl), _ = jax.lax.scan(block, (top, top), blocks)
        return bh, bl

    orbit_fp.forms = forms
    return orbit_fp


# ---------------------------------------------------------------------------
# Orbit keys of a spec declared as frontend data.  A schema names its
# symmetric sorts and says, a field, which axes a sort indexes and whether
# its contents are members of one (frontend/schema.Sort / Over): from that
# alone, the plain loop that *is* the key (permute, pack, fingerprint,
# least) and the device form of it.  Every field such a schema can declare
# is a flag or a small int that a permutation moves or relabels, so the
# whole key before its finaliser is ``features . table``: no bag to rank and
# nothing to move, and the limbs, the block product and the least key below
# are the functions Raft's scan runs.
# ---------------------------------------------------------------------------

def schema_group(schema, bounds: Bounds, sorts: tuple) -> tuple:
    """The group the named sorts span, ``{sort: permutation}`` an element
    (``p[j]`` = what member j becomes), the identity first; the sorts in
    the schema's order, the last one's permutations innermost.  A name the
    schema does not declare is refused."""
    extra = sorted(set(sorts) - set(schema.sort_names))
    if extra:
        raise ValueError(
            f"schema {schema.name!r} declares no symmetric sort "
            f"{extra[0]!r} (declared: "
            f"{', '.join(schema.sort_names) or 'none'})")
    sizes = schema.layout(bounds).sort_sizes
    per = []
    for name in schema.sort_names:
        if name not in sorts:
            continue
        k = sizes[name]
        if k > MAX_SYM_SERVERS:
            raise ValueError(
                f"{name} symmetry supports at most {MAX_SYM_SERVERS} "
                f"members (got {k}: {math.factorial(k)} permutations)")
        per.append([(name, p) for p in itertools.permutations(range(k))])
    return tuple(dict(g) for g in itertools.product(*per))


def permute_schema_struct(struct: dict, lay, g: dict, xp) -> dict:
    """The image of a schema-declared struct (leading batch dims pass
    through) under one element of :func:`schema_group`: an axis a sort
    indexes is reordered (the image's index ``img[i]`` holds the state's
    ``i``), contents that are members of a sort are relabelled."""
    out = {}
    for f in lay.schema.fields:
        a, shp = struct[f.name], lay.shapes[f.name]
        for axis, over in f.overs():
            if over.sort in g:
                back = np.argsort(over.image(g[over.sort], shp[axis]))
                a = xp.take(a, xp.asarray(back), axis=axis - len(shp))
        if f.content is not None and f.content.sort in g:
            lut = f.content.image(g[f.content.sort], lay.his[f.name] + 1)
            a = xp.asarray(lut.astype(np.int32))[a]
        out[f.name] = a
    return out


def schema_orbit_fingerprint(struct: dict, lay, consts, sorts: tuple, xp):
    """Orbit-minimal (hi, lo) fingerprint of schema-declared struct(s): the
    least key over the images under every element of the named sorts'
    group.  The definition; :func:`build_schema_orbit_fp` is held to it
    bit for bit."""
    best = None
    for g in schema_group(lay.schema, lay.bounds, sorts):
        best = _lex_min(best, fpr.fingerprint(
            lay.pack(permute_schema_struct(struct, lay, g, xp), xp),
            consts, xp), xp)
    return best


def _schema_features(struct: dict, lay, xp):
    """``struct[N, ...] -> int8[F, N]``: what no group element changes, in
    :func:`_schema_key_table`'s order, lanes minor.  A field whose
    contents a sort relabels gives one feature a position and a value (its
    one-hot, 0 left out: it adds nothing to a sum); every other field the
    word itself, which the fold leaves alone (declared within 0..127:
    :func:`_schema_feature_cap`)."""
    parts = []
    for f in lay.schema.fields:
        a = struct[f.name]
        if f.content is not None:
            a = a[..., None] == xp.arange(1, lay.his[f.name] + 1)
        a = xp.reshape(a, (a.shape[0], -1))
        parts.append(a.astype(xp.int8))     # 7-bit: the feature cap is 127
    return xp.concatenate(parts, axis=1).T


def _schema_feature_cap(lay) -> int:
    """The largest entry :func:`_schema_features` can hold; a field the
    linear key cannot carry (a negative value, which the fold would not
    leave alone) is refused by name."""
    cap = 1
    for f in lay.schema.fields:
        if f.lo < 0:
            raise ValueError(
                f"orbit key: field {f.name!r} of schema "
                f"{lay.schema.name!r} may hold {f.lo} < 0")
        if f.content is None:
            cap = max(cap, lay.his[f.name])
    return cap


def _schema_key_table(lay, consts, group: tuple) -> np.ndarray:
    """``uint32[|G|, 2, F]``: for each group element the two lanes'
    constants of :func:`_schema_features`' entries, such that ``features .
    table[g]`` (mod 2^32) is the sum before the finaliser of
    ``fingerprint(pack(permute_schema_struct(s, g)))``: the constant of
    the position a word moves to, times the value it is relabelled to
    where the feature is a content's one-hot."""
    fc = fpr.field_constants(lay.shapes, consts)
    table = []
    for g in group:
        row = []
        for f in lay.schema.fields:
            cf, shp = fc[f.name], lay.shapes[f.name]
            for axis, over in f.overs():
                if over.sort in g:
                    cf = np.take(cf, over.image(g[over.sort], shp[axis]),
                                 axis=1 + axis)
            if f.content is not None:
                hi = lay.his[f.name]
                to = f.content.image(g[f.content.sort], hi + 1) \
                    if f.content.sort in g else np.arange(hi + 1)
                cf = cf[..., None] * to[1:].astype(np.uint32)
            row.append(cf.reshape(2, -1))
        table.append(np.concatenate(row, axis=1))
    return np.stack(table)


def build_schema_orbit_fp(schema, bounds: Bounds, sorts: tuple, consts):
    """Batched orbit-minimal fingerprints of a schema-declared spec:
    ``struct[N, ...] -> (hi, lo)[N]``, bit-identical to
    :func:`schema_orbit_fingerprint`.  :func:`build_orbit_fp`'s linear
    part, and nothing else: ``int8`` features once a call
    (:func:`_schema_features`), the host-built table of permuted constants
    a group element (:func:`_schema_key_table`) split into ``int8`` limbs,
    one ``dot_general`` a block of images (:func:`_block_perms`), the
    limbs shifted home, the finaliser, the least ``(hi, lo)`` — a
    ``lax.scan`` over the blocks, the program's size constant in |G|."""
    import jax
    import jax.numpy as jnp

    lay = schema.layout(bounds)
    group = schema_group(schema, bounds, sorts)
    table = _schema_key_table(lay, np.asarray(consts), group)
    _check_limb_range(table.shape[-1], _schema_feature_cap(lay))
    limbs = jnp.asarray(_key_limbs(table))               # [G, 2, 4, F]

    def orbit_fp(struct):
        phi = _schema_features(struct, lay, jnp)
        Gb = _block_perms(len(group), phi.shape[1])

        def block(best, block_limbs):
            sums = _limb_sums(block_limbs, phi, jnp)     # [G_b, 2, N]
            hi, lo = fpr.finalise(sums[:, 0], sums[:, 1], jnp)
            return _least_image(best, hi, lo), None

        # +inf derived from the input, as build_orbit_fp's (shard_map)
        top = jnp.zeros_like(phi[0]).astype(jnp.uint32) \
            | jnp.uint32(0xFFFFFFFF)
        (bh, bl), _ = jax.lax.scan(
            block, (top, top),
            limbs.reshape((len(group) // Gb, Gb) + limbs.shape[1:]))
        return bh, bl

    return orbit_fp


def orbit_fingerprint(struct: dict, bounds: Bounds, consts, xp,
                      axes: tuple = ("Server",)):
    """Orbit-minimal (hi, lo) fingerprint of one canonical state struct,
    minimized over the permutation group of the named ``axes``."""
    sperms = permutations(bounds) if "Server" in axes \
        else (tuple(range(bounds.n_servers)),)
    vqs = range(len(value_permutations(bounds))) if "Value" in axes else (0,)
    best = None
    for p in sperms:
        ps = permute_struct(struct, p, bounds, xp)
        for qi in vqs:
            t = permute_values(ps, qi, bounds, xp) if "Value" in axes else ps
            t = st.canonicalize(t, xp)
            best = _lex_min(best, fpr.fingerprint(st.pack(t, xp), consts,
                                                  xp), xp)
    return best


@functools.lru_cache(maxsize=None)
def _host_consts(width: int) -> np.ndarray:
    # one PCG64 spin-up per width, not per call (refbfs keys every
    # transition through here under symmetry)
    return fpr.lane_constants(width)


def py_orbit_fingerprint(s, bounds: Bounds,
                         axes: tuple = ("Server",)) -> tuple:
    """Oracle-side orbit key of a PyState — same arithmetic, NumPy."""
    from raft_tla_tpu.models import interp

    lay = st.Layout.of(bounds)
    struct = st.unpack(interp.to_vec(s, bounds), lay, np)
    hi, lo = orbit_fingerprint(struct, bounds, _host_consts(lay.width), np,
                               axes)
    return int(hi), int(lo)


def init_fingerprint(config, init_py, init_vec) -> tuple:
    """The dedup key of the initial state, view-folded and orbit-reduced
    per the config — one definition for every engine's table seeding."""
    if getattr(config, "view", None):
        from raft_tla_tpu.models import interp, views

        viewed = views.py_view(config.view)(init_py, config.bounds)
        if viewed is not init_py:
            init_py = viewed
            init_vec = interp.to_vec(viewed, config.bounds)
    if config.symmetry:
        return py_orbit_fingerprint(init_py, config.bounds, config.symmetry)
    consts = _host_consts(init_vec.shape[-1])
    hi, lo = fpr.fingerprint(init_vec.astype(np.int32), consts, np)
    return int(hi), int(lo)

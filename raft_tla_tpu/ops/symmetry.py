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
orbit is whichever member was discovered first).  On device this is |π|
static transforms batched over the candidate block — pure gathers, bit
arithmetic, and the existing canonicalize/fingerprint pipeline (the key of
each image is taken from its fields, never from a packed row).

Permuting one state under ``p`` (new index of old server j is ``p[j]``):

- per-server axes (role, term, votedFor, commitIndex, logLen, log*,
  vResp, vGrant): rows reordered by the inverse permutation;
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


def _server_luts(bounds: Bounds) -> tuple:
    """Stacked lookup tables for every server permutation — the data that
    lets ONE compiled transform apply any group element (build_orbit_fp):
    ``inv_idx [P, n]`` row gathers, ``vf_map [P, n+1]`` votedFor relabel,
    ``bit_lut [P, 2^n]`` vote-bitmask permutation, ``p_lut [P, 16]``
    message src/dst relabel (4-bit fields)."""
    ps = permutations(bounds)
    n = bounds.n_servers
    P = len(ps)
    inv_idx = np.empty((P, n), np.int32)
    vf_map = np.empty((P, n + 1), np.int32)
    bit_lut = np.empty((P, 1 << n), np.int32)
    p_lut = np.zeros((P, 16), np.int32)
    masks = np.arange(1 << n, dtype=np.int64)
    for i, p in enumerate(ps):
        inv_idx[i] = [p.index(k) for k in range(n)]
        vf_map[i] = (0,) + tuple(p[j] + 1 for j in range(n))
        bm = np.zeros((1 << n,), np.int64)
        for j in range(n):
            bm |= ((masks >> j) & 1) << p[j]
        bit_lut[i] = bm
        p_lut[i, :n] = p
    return inv_idx, vf_map, bit_lut, p_lut


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


def _permute_struct_batch(struct: dict, inv, vf_map, bit_lut, p_lut, xp):
    """``permute_struct`` over a leading batch axis, with the permutation
    given as traced LUT rows (same arithmetic, same bits — the gathers
    read precomputed tables instead of Python-side tuples)."""
    def rows(a):
        return xp.take(a, inv, axis=1)

    s_sh, s_w = mb._HI_FIELDS["src"]
    d_sh, d_w = mb._HI_FIELDS["dst"]
    keep = ~(((1 << s_w) - 1) << s_sh | ((1 << d_w) - 1) << d_sh)
    hi = struct["msgHi"]
    occupied = struct["msgCount"] > 0
    new_hi = (hi & keep) \
        | (p_lut[(hi >> s_sh) & ((1 << s_w) - 1)] << s_sh) \
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
        "vResp": bit_lut[rows(struct["vResp"])],
        "vGrant": bit_lut[rows(struct["vGrant"])],
        "nextIndex": xp.take(rows(struct["nextIndex"]), inv, axis=2),
        "matchIndex": xp.take(rows(struct["matchIndex"]), inv, axis=2),
        "msgHi": new_hi,
        "msgLo": struct["msgLo"],
        "msgCount": struct["msgCount"],
    }
    if "eTerm" in struct:
        eocc = struct["eTerm"] > 0
        out.update({
            "allLogs": struct["allLogs"],
            "vLog": xp.take(rows(struct["vLog"]), inv, axis=2),
            "eTerm": struct["eTerm"],
            "eLeader": xp.where(eocc, p_lut[struct["eLeader"]],
                                struct["eLeader"]),
            "eLog": struct["eLog"],
            "eVotes": xp.where(eocc, bit_lut[struct["eVotes"]],
                               struct["eVotes"]),
            "eVLog": xp.take(struct["eVLog"], inv, axis=2),
        })
    return out


def _permute_values_batch(struct: dict, luts: dict, qi, bounds: Bounds, xp):
    """``permute_values`` over a leading batch axis with traced LUT rows."""
    vlut = luts["vlut"][qi]
    e_lut = luts["e_lut"][qi]
    e_sh, e_w = mb._LO_FIELDS["e"]
    lo = struct["msgLo"]
    out = dict(struct)
    out["logVal"] = vlut[struct["logVal"]]
    new_lo = (lo & ~(((1 << e_w) - 1) << e_sh)) \
        | (e_lut[(lo >> e_sh) & ((1 << e_w) - 1)] << e_sh)
    if "allLogs" in struct:
        rmap = luts["rmap"][qi]
        rlut1 = luts["rlut1"][qi]
        g_lut = luts["g_lut"][qi]
        U = int(rmap.shape[0])
        out["vLog"] = rlut1[struct["vLog"]]
        out["eLog"] = rmap[struct["eLog"]]
        out["eVLog"] = rlut1[struct["eVLog"]]
        g_sh, g_w = mb._LO_FIELDS["g"]
        new_lo = (new_lo & ~(((1 << g_w) - 1) << g_sh)) \
            | (g_lut[(new_lo >> g_sh) & ((1 << g_w) - 1)] << g_sh)
        # allLogs bit-permute, batched (same sum-as-OR trick as
        # permute_values; sign bit handled separately — no x64 under jit)
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
        out["allLogs"] = (low.astype(xp.int32)
                          | xp.where(top, xp.asarray(-2**31, xp.int32), 0))
    occupied = struct["msgCount"] > 0
    out["msgLo"] = xp.where(occupied, new_lo, struct["msgLo"])
    return out


def build_orbit_fp(bounds: Bounds, axes: tuple, consts, faithful: bool):
    """Batched orbit-minimal fingerprints: ``struct[N, ...] -> (hi, lo)[N]``.

    Bit-identical to :func:`orbit_fingerprint` (same permute/canonicalize
    arithmetic, and the key of each image taken from its fields,
    ``fingerprint_fields``, which equals the fingerprint of the packed
    row the loop builds; the (hi, lo) lexicographic min is
    order-independent) but compiled as ONE transform iterated by
    ``lax.scan`` over the |G| = n!·V! group elements, instead of |G|
    unrolled copies of the pipeline.  The round-1 unrolled graph at five
    servers (120 copies) crashed compiles at chunk 2048 and capped the
    elect5 run at ~3k orbits/s; the scan keeps the program size constant
    in |G| so large chunks compile and the VPU sees one tight loop.
    """
    import jax
    import jax.numpy as jnp

    sluts = tuple(jnp.asarray(a) for a in _server_luts(bounds)) \
        if "Server" in axes else None
    vluts = {k: jnp.asarray(v)
             for k, v in _value_luts(bounds, faithful).items()} \
        if "Value" in axes else None
    P = len(permutations(bounds)) if "Server" in axes else 1
    Q = len(value_permutations(bounds)) if "Value" in axes else 1

    def canon_fp(s):
        # the key of one group element's image, from its fields: the
        # scan body never builds the packed row (a concatenate and two
        # relayouts through HBM every iteration; PERF.md, PR 27)
        return fpr.fingerprint_fields(st.canonicalize(s, jnp), consts, jnp)

    def orbit_fp(struct):
        def body(best, k):
            pi, qi = k // Q, k % Q
            t = struct
            if sluts is not None:
                inv_idx, vf_map, bit_lut, p_lut = sluts
                t = _permute_struct_batch(t, inv_idx[pi], vf_map[pi],
                                          bit_lut[pi], p_lut[pi], jnp)
            if vluts is not None:
                t = _permute_values_batch(t, vluts, qi, bounds, jnp)
            hi, lo = jax.vmap(canon_fp)(t)
            bh, bl = best
            take = (hi < bh) | ((hi == bh) & (lo < bl))
            return (jnp.where(take, hi, bh), jnp.where(take, lo, bl)), None

        # derive the +inf init from the input so it inherits the input's
        # varying manual axes — a constant-built carry breaks the scan
        # type match when this runs inside shard_map (CP lane sharding)
        top = jnp.zeros_like(struct["role"][:, 0]).astype(jnp.uint32) \
            | jnp.uint32(0xFFFFFFFF)
        init = (top, top)
        (bh, bl), _ = jax.lax.scan(body, init,
                                   jnp.arange(P * Q, dtype=jnp.int32))
        return bh, bl

    return orbit_fp


def orbit_fingerprint(struct: dict, bounds: Bounds, consts, xp,
                      axes: tuple = ("Server",)):
    """Orbit-minimal (hi, lo) fingerprint of one canonical state struct,
    minimized over the permutation group of the named ``axes``."""
    sperms = permutations(bounds) if "Server" in axes \
        else (tuple(range(bounds.n_servers)),)
    vqs = range(len(value_permutations(bounds))) if "Value" in axes else (0,)
    best_hi = best_lo = None
    for p in sperms:
        ps = permute_struct(struct, p, bounds, xp)
        for qi in vqs:
            t = permute_values(ps, qi, bounds, xp) if "Value" in axes else ps
            t = st.canonicalize(t, xp)
            hi, lo = fpr.fingerprint(st.pack(t, xp), consts, xp)
            if best_hi is None:
                best_hi, best_lo = hi, lo
            else:
                take = (hi < best_hi) | ((hi == best_hi) & (lo < best_lo))
                best_hi = xp.where(take, hi, best_hi)
                best_lo = xp.where(take, lo, best_lo)
    return best_hi, best_lo


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

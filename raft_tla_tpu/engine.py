"""Single-chip BFS engine — the L4 checker runtime (SURVEY §7.1 step 5).

Plays the role TLC plays for the reference (SURVEY §0): level-synchronous
breadth-first exploration from ``Init`` (``raft.tla:155-160``) of the
transition graph of ``Next`` (``raft.tla:454-465``), deduplicating states by
64-bit fingerprint, checking invariants on every distinct state, gating
expansion on the StateConstraint (violating states are counted and
invariant-checked but never expanded — TLC CONSTRAINT semantics), and
reconstructing a counterexample trace on violation.

TPU-native structure:

- The hot loop is one fused, jitted computation per frontier chunk
  (``ops/kernels.build_step``): unpack → batched guarded transitions for the
  whole action table → canonicalize → pack → fingerprint → invariant +
  constraint predicates.  One device round-trip per chunk.
- Fixed chunk size ⇒ exactly one compiled executable; the last chunk is
  padded (XLA static shapes, SURVEY §7.2.4).
- Dedup v1 is a host-side fingerprint set: only the (small) fingerprint /
  mask lanes come back per chunk; the (wide) successor vectors are gathered
  on device for *new* states only before transfer.  The device-resident
  hash-table dedup is layered on in ``parallel/`` — this module is the
  correctness anchor it is differentially tested against.

Discovery order is byte-identical to the oracle's (``models/refbfs.py``):
frontier states in insertion order × action lanes in ``spec.action_table``
order.  That makes state counts, per-level counts, coverage counters, and
the *first* invariant violation all exactly comparable.

Fingerprint collisions merge states (probabilistically negligible, the same
regime TLC's FP64 operates in — SURVEY §2.8); the oracle-parity tests run on
spaces small enough that a collision would be detected as a count mismatch.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from raft_tla_tpu.config import CheckConfig
from raft_tla_tpu.models import interp
from raft_tla_tpu.ops import fingerprint as fpr


from raft_tla_tpu.models.refbfs import DEADLOCK  # noqa: E402  (sentinel)


@dataclasses.dataclass
class Violation:
    invariant: str          # registry name, or refbfs.DEADLOCK
    state: interp.PyState
    # Trace from Init: [(action_label | None, PyState)]; replayable by interp.
    trace: list


@dataclasses.dataclass
class EngineResult:
    n_states: int          # distinct states found (incl. constraint-violating)
    diameter: int          # BFS levels past Init that produced new states
    n_transitions: int     # enabled (state, action) pairs explored
    coverage: Counter      # action family -> distinct new states produced
    violation: Optional[Violation]
    levels: list           # new-state count per level (levels[0] = 1)
    wall_s: float
    # False only for runs stopped before the search's end (the ddd
    # engines' deadline_s / SIGINT stop); every exhaustive verdict above
    # requires complete=True.
    complete: bool = True
    # the pass ledger's record of this check() (obs/passlog.py: one entry a
    # level, the head, the tail, the workers' seams), shared with the
    # ledger: read it, do not change it.  The ddd engines fill it; the
    # engines that keep no ledger leave it None.
    level_log: Optional[dict] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def states_per_sec(self) -> float:
        return self.n_states / self.wall_s if self.wall_s > 0 else float("inf")


class _VecStore:
    """Append-only host store of packed state vectors, random-access by index.

    Plays the role of TLC's ``states/`` directory (``.gitignore:2``) for trace
    reconstruction: every accepted state's vector is kept, addressed by its
    global discovery index.  Chunked append keeps inserts O(1) amortized.
    """

    def __init__(self, width: int):
        self._chunks: list[np.ndarray] = []
        self._offsets = [0]
        self._width = width

    def append(self, rows: np.ndarray) -> None:
        if rows.size:
            self._chunks.append(np.ascontiguousarray(rows, dtype=np.int32))
            self._offsets.append(self._offsets[-1] + rows.shape[0])

    def __len__(self) -> int:
        return self._offsets[-1]

    def get(self, idx: int) -> np.ndarray:
        import bisect
        c = bisect.bisect_right(self._offsets, idx) - 1
        return self._chunks[c][idx - self._offsets[c]]


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


class Engine:
    """Compiled checker for one :class:`CheckConfig`. Reusable across runs."""

    def __init__(self, config: CheckConfig, model=None):
        from raft_tla_tpu.frontend import resolve_model
        self.config = config
        self.bounds = config.bounds
        self.model = model if model is not None \
            else resolve_model(config.spec)
        self.lay = self.model.layout(self.bounds)
        self.table = self.model.action_table(self.bounds)
        self.A = len(self.table)
        self.chunk = config.chunk
        self._step = jax.jit(self.model.build_step(config))

    # -- public API ----------------------------------------------------------

    def check(self, max_states: int | None = None,
              init_override: interp.PyState | None = None,
              progress=None) -> EngineResult:
        """Exhaustively explore; stop at the first invariant violation.

        ``init_override`` mirrors the oracle's hook (``refbfs.check``).
        ``progress`` is an optional callback ``(level, n_states, frontier)``.
        """
        t0 = time.monotonic()
        cfg, bounds, lay = self.config, self.bounds, self.lay
        B, A, W = self.chunk, self.A, self.lay.width
        inv_names = list(cfg.invariants)

        init_py = init_override if init_override is not None \
            else self.model.init_py(bounds)
        init_vec = self.model.to_vec(init_py, bounds)
        hi0, lo0 = self.model.init_fingerprint(self.config, init_py,
                                               init_vec)
        init_key = int(fpr.to_u64(hi0, lo0))

        seen: set[int] = {init_key}
        store = _VecStore(W)
        store.append(init_vec[None, :])
        parents: list = [None]               # global idx -> (parent, lane) | None
        con_flags = [self.model.constraint_ok(init_py, bounds)]
        coverage: Counter = Counter()
        levels = [1]
        n_transitions = 0
        violation: Optional[Violation] = None

        for nm in inv_names:
            if not self.model.py_invariant(nm)(init_py, bounds):
                violation = self._make_violation(nm, 0, store, parents)
                break

        # frontier: list of global indices of states to expand this level
        frontier = [0] if violation is None and con_flags[0] else []

        while frontier and violation is None:
            new_this_level = 0
            next_frontier: list[int] = []
            for c0 in range(0, len(frontier), B):
                gidx = frontier[c0:c0 + B]
                nb = len(gidx)
                vecs = np.stack([store.get(g) for g in gidx])
                if nb < B:   # pad to the static chunk shape
                    pad = np.broadcast_to(vecs[0], (B - nb, W))
                    vecs = np.concatenate([vecs, pad], axis=0)
                out = self._step(jnp.asarray(vecs))

                valid = np.asarray(out["valid"])[:nb]          # [nb, A]
                ovf = np.asarray(out["overflow"])[:nb]
                keys = fpr.to_u64(np.asarray(out["fp_hi"])[:nb],
                                  np.asarray(out["fp_lo"])[:nb])
                inv_ok = np.asarray(out["inv_ok"])[:nb]        # [nb, A, nI]
                con_ok = np.asarray(out["con_ok"])[:nb]

                if ovf.any():
                    b, a = np.argwhere(ovf)[0]
                    raise RuntimeError(
                        "state-capacity overflow at "
                        f"{self.table[int(a)].label()} — bounds reasoning "
                        "violated (config.py capacity scheme)")
                # TLC's default deadlock check: an expanded state with no
                # successor (stuttering excluded).  Successors of earlier
                # rows in the chunk are recorded first — refbfs order.
                dead_limit = None
                if cfg.check_deadlock:
                    dead = ~valid.any(axis=1)
                    if dead.any():
                        fb = int(np.argmax(dead))
                        dead_limit = fb * A

                # Dedup in discovery order: flat index = b * A + a.
                flat_keys = keys.reshape(-1)
                flat_valid = valid.reshape(-1)
                if dead_limit is not None:
                    flat_valid = flat_valid.copy()
                    flat_valid[dead_limit:] = False
                # Count transitions AFTER the dead-state truncation so the
                # stats stay refbfs-exact on deadlock counterexamples (the
                # oracle stops counting at the first dead state).
                n_transitions += int(flat_valid.sum())
                cand = np.nonzero(flat_valid)[0]
                new_flat: list[int] = []
                for fi in cand:
                    kk = int(flat_keys[fi])
                    if kk in seen:
                        continue
                    seen.add(kk)
                    new_flat.append(int(fi))
                # Truncate at the first violating new state so stats match
                # refbfs exactly: the oracle stops recording the instant it
                # sees a violation, mid-chunk included.
                for t, fi in enumerate(new_flat):
                    b, a = divmod(fi, A)
                    if not inv_ok[b, a].all():
                        new_flat = new_flat[:t + 1]
                        break
                if not new_flat:
                    if dead_limit is not None:
                        violation = self._make_violation(
                            DEADLOCK, gidx[dead_limit // A], store, parents)
                        break
                    continue

                nf = np.asarray(new_flat, dtype=np.int64)
                # Device-side gather of just the new rows (padded to a pow2
                # bucket so the eager gather compiles O(log) distinct shapes).
                cap = _next_pow2(max(len(nf), 1))
                sel = np.concatenate(
                    [nf, np.zeros(cap - len(nf), dtype=np.int64)])
                rows = np.asarray(out["svecs"].reshape(B * A, W)
                                  [jnp.asarray(sel)])[:len(nf)]

                base = len(store)
                store.append(rows)
                for t, fi in enumerate(new_flat):
                    b, a = divmod(fi, A)
                    g = base + t
                    parents.append((gidx[b], int(a)))
                    coverage[self.table[int(a)].family] += 1
                    new_this_level += 1
                    c_ok = bool(con_ok[b, a])
                    con_flags.append(c_ok)
                    bad = np.nonzero(~inv_ok[b, a])[0]
                    if bad.size:
                        violation = self._make_violation(
                            inv_names[int(bad[0])], g, store, parents)
                        break
                    if c_ok:
                        next_frontier.append(g)
                if violation is None and dead_limit is not None:
                    violation = self._make_violation(
                        DEADLOCK, gidx[dead_limit // A], store, parents)
                if violation is not None:
                    break
            if violation is not None:
                break
            if max_states is not None and len(store) > max_states:
                raise RuntimeError(f"state count exceeded {max_states}")
            if new_this_level:
                levels.append(new_this_level)
            if progress is not None:
                progress(len(levels) - 1, len(store), len(next_frontier))
            frontier = next_frontier

        return EngineResult(
            n_states=len(store),
            diameter=len(levels) - 1,
            n_transitions=n_transitions,
            coverage=coverage,
            violation=violation,
            levels=levels,
            wall_s=time.monotonic() - t0,
        )

    # -- internals -----------------------------------------------------------

    def _make_violation(self, inv_name: str, gidx: int, store: _VecStore,
                        parents: list) -> Violation:
        """Walk the parent chain back to Init (TLC's counterexample trace)."""
        chain = []
        cur: Optional[int] = gidx
        while cur is not None:
            py = self.model.from_vec(store.get(cur), self.bounds)
            entry = parents[cur]
            label = self.table[entry[1]].label() if entry else None
            chain.append((label, py))
            cur = entry[0] if entry else None
        chain.reverse()
        return Violation(invariant=inv_name, state=chain[-1][1], trace=chain)


def check(config: CheckConfig, **kw) -> EngineResult:
    """One-shot convenience: build the engine and run it."""
    return Engine(config).check(**kw)

"""Sharded, fused audit dispatch: match-kernel ∧ template-programs over a
device mesh.

Design (SURVEY §2.4 rows 1/4; reference counterpart: the per-pod
replicated OPA state + per-object serial loop in pkg/audit/manager.go:
277-335, which has no intra-query parallelism at all):

  * 2-D mesh ``("c", "n")`` — constraints × resources. The resource axis
    ("n") is the big one and the default shard target; the constraint
    axis ("c") is available for very large constraint populations
    (c_shards=1 gives the plain 1-D resource shard).
  * Policy-side tensors (match specs, program consts, string tables) are
    replicated — they are small. Resource-side tensors (token table,
    review features) are sharded on "n".
  * The match matrix and every compiled template program evaluate in ONE
    jitted dispatch; XLA partitions the elementwise [C, N] work with no
    communication, and the only collective is the reduction that
    produces per-constraint violation totals (an all-reduce over the "n"
    axis inserted by GSPMD). Violation *indices* leave the device as the
    sparse (c, n) set — the all-gather the north star prescribes.

Everything is shape-padded to mesh-divisible sizes host-side; padded
constraint rows are all-pad (-1) kind selectors which match nothing, and
padded resource rows are sliced off after gather.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.matchkernel import match_matrix
from ..engine.programs import Program
from ..engine.patterns import PatternRegistry
from ..engine.tables import StrTables


def audit_mesh(
    n_devices: Optional[int] = None, c_shards: int = 1
) -> Mesh:
    """A ("c", "n") mesh over the first n_devices devices; c_shards
    splits the constraint axis (1 = resource-axis sharding only)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    if len(devs) % c_shards != 0:
        raise ValueError(
            f"{len(devs)} devices not divisible by c_shards={c_shards}"
        )
    arr = np.array(devs).reshape(c_shards, len(devs) // c_shards)
    return Mesh(arr, ("c", "n"))


def _get_overlapped(out):
    """Fetch a pytree of device arrays in ONE round trip: start every
    device->host copy async, then materialize. jax.device_get alone
    copies leaf-by-leaf, paying one host<->device round trip per
    leaf."""
    for x in jax.tree_util.tree_leaves(out):
        try:
            x.copy_to_host_async()
        except Exception:
            pass
    return jax.device_get(out)


def _g01(g) -> Tuple[int, int]:
    """Fanout argument: an int (g0 == g1 == g, legacy) or (g0, g1)."""
    if isinstance(g, tuple):
        return int(g[0]), int(g[1])
    return int(g), int(g)


def _pad_len(n: int) -> int:
    """Padded vocab-axis capacity: next power of two with headroom for
    at least one full delta chunk, so growth stays in-bucket for a
    while and jit shapes stay stable."""
    p = 4096
    while p < n + FusedAuditKernel._DELTA_ROWS:
        p *= 2
    return p


def _pad_axis(a: np.ndarray, axis: int, mult: int, fill) -> np.ndarray:
    n = a.shape[axis]
    target = ((n + mult - 1) // mult) * mult
    if target == n:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, target - n)
    return np.pad(a, widths, constant_values=fill)


class ColdKernel(Exception):
    """Raised by dispatch with require_compiled=True when the needed jit
    entry does not exist yet — the caller serves on the interpreter and
    compiles in the background (serve-while-compiling)."""


@dataclass
class StagedPolicy:
    """Constraint-side tensors resident on device (staged once per
    constraint-set change): match specs, grouped program consts, and the
    compiled-constraint mask.

    Both constraint-side inputs are content-deduplicated: `ms_dev` holds
    only the U distinct match-spec rows (+1 match-nothing row for padded
    constraints) with `spec_map` [C_pad] scattering them back, and each
    program group's `stacked_consts` holds only its distinct const sets
    with `group_cmaps` mapping constraint rows to them. Gatekeeper
    populations are dedup-friendly by construction — constraints of one
    template share spec.match blocks and parameter sets — so the O(C x N)
    selector/program work collapses to O(U x N) + a row gather."""

    ms_dev: Dict[str, Any]  # [U+1, ...] replicated
    spec_map: Any  # [C_pad] int32 device, "c"-sharded
    n_specs: int  # U (excluding the match-nothing row)
    group_exprs: List[Any]
    group_rows: List[List[int]]
    group_cmaps: List[List[int]]  # per group: row -> unique-const index
    stacked_consts: List[Dict[str, Any]]
    compiled_mask: Any  # [C_pad] bool device
    prog_rows: List[int]
    c: int  # true constraint count
    c_pad: int
    key: Tuple


@dataclass
class StagedBatch:
    """Resource-side tensors resident on device (staged once per corpus
    chunk): review features, token table, and the row-fallback mask."""

    fb_dev: Dict[str, Any]
    tok_dev: Dict[str, Any]
    row_fb: Any  # [N_pad] bool device
    n_valid: int  # true rows in this chunk
    key: Tuple


@dataclass
class StackedCorpus:
    """The whole corpus resident on device as [K, chunk, ...] stacked
    tensors, so a full sweep is ONE device execution (a lax.map over the
    chunk axis) and ONE host fetch, where per-chunk dispatches would
    each pay a host<->device round trip."""

    fb_dev: Dict[str, Any]  # [K, chunk, ...]
    tok_dev: Dict[str, Any]  # [K, chunk, ...]
    row_fb: Any  # [K, chunk] bool device
    n_valid: Any  # [K] int32 device (runtime occupancy per chunk)
    n_valids: List[int]  # host copy
    k: int
    chunk: int
    key: Tuple
    # per-row feature planes ([K, chunk] bool device), e.g. the
    # inventory join-key duplication bits (stage_row_feats)
    row_dev: Dict[str, Any] = None
    # ephemeral vocab-overlay blocks (webhook batches): "member"/
    # "capture" [B, P] + per-kind [B, T] slabs; ids >= v_base resolve
    # against these instead of the resident tables
    ov_dev: Optional[Dict[str, Any]] = None
    v_base: int = 0


class FusedAuditKernel:
    """One-dispatch audit: [C, N] match ∧ per-program violation counts.

    With a mesh, inputs are placed with NamedShardings and GSPMD
    partitions the compute; without one, it is the plain single-device
    fused dispatch (what TpuDriver uses for its steady-state sweep).

    Two dispatch forms:
      * `run`/`prepare` — full [C, N] outputs (dryrun/entry/tests);
      * `stage_policy`/`stage_corpus_stacked`/`dispatch_need_all` —
        device-resident
        operands + sparse output: only the flat indices of pairs that
        need host-side interpreter work leave the device (the all-gather
        of violation indices the north star prescribes; gathering the
        full matrices over the chip link is what made sweeps slow).
    """

    def __init__(
        self,
        patterns: PatternRegistry,
        tables: StrTables,
        mesh: Optional[Mesh] = None,
    ):
        self.patterns = patterns
        self.tables = tables
        self.mesh = mesh
        # optional MetricsRegistry: per-dispatch program-cache hit/miss
        # counters + compile-time distributions (TpuDriver.set_metrics
        # wires this; kernel telemetry is how a p99 cliff gets blamed
        # on XLA compiles vs device execution)
        self.metrics = None
        # key -> [closure, jitted|None]: one entry per distinct
        # (group-set, shapes, n, g) specialization
        self._jit_cache: Dict[Tuple, List[Any]] = {}
        self._table_cache: Optional[Tuple[Tuple[int, int], Dict[str, Any]]] = None
        self._fused_cols: Dict[str, Dict[Any, int]] = {}
        # delta-resident table buffers: name -> [device buf (vocab axis
        # padded), filled rows, non-vocab dims]. Steady-state vocab
        # growth (every admission batch interns new object names) ships
        # only the NEW rows to the device and leaves jit shapes stable —
        # without this, each webhook batch re-uploaded every table and
        # recompiled on the changed shapes
        self._resident: Dict[str, Tuple[Any, int, Tuple]] = {}

    # -- shardings -----------------------------------------------------------

    # delta-upload granularity for vocab-axis table growth: deltas pad
    # to multiples of this, so at most a handful of distinct jit shapes.
    # Small on purpose: a webhook batch interns only a few hundred new
    # vocab entries
    _DELTA_ROWS = 512

    def _note_cache(self, op: str, result: str) -> None:
        """program_cache_total{op, result=hit|miss|cold}: every jit
        specialization lookup. `cold` = require_compiled found no entry
        (the serve-while-compiling bounce to the interpreter)."""
        if self.metrics is not None:
            self.metrics.record(
                "program_cache_total", 1, op=op, result=result
            )

    def _note_compile(self, op: str, seconds: float) -> None:
        """First-call wall time of a fresh jit entry — trace + XLA
        compile (jax.jit compiles synchronously inside the first call;
        result arrays come back async, so execution is excluded)."""
        if self.metrics is not None:
            self.metrics.observe("program_compile_seconds", seconds, op=op)

    def _spec(self, *axes) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*axes))

    def _put(self, x, *axes):
        arr = jnp.asarray(x)
        s = self._spec(*axes)
        return arr if s is None else jax.device_put(arr, s)

    def _put_group(self, arrays, *axes):
        """Device-put a dict of host arrays minimizing TRANSFERS:
        same-(dtype, shape) entries ship as ONE stacked buffer and
        unstack into device views. Host->device hops dominate the
        webhook batch staging (each put is a separate latency-bound
        transfer; a review batch stages ~20 small arrays)."""
        out = {}
        groups: Dict[Tuple, List[str]] = {}
        for k, v in arrays.items():
            a = np.asarray(v)
            groups.setdefault((str(a.dtype), a.shape), []).append(k)
        for (_, _shape), names in groups.items():
            if len(names) == 1:
                out[names[0]] = self._put(arrays[names[0]], *axes)
                continue
            stacked = np.stack([np.asarray(arrays[n]) for n in names])
            buf = self._put(stacked, None, *axes)
            for i, n in enumerate(names):
                out[n] = buf[i]
        return out

    def _tables_device(self) -> Dict[str, Any]:
        self.patterns.sync()
        self.tables.sync()
        gen = (self.patterns.generation, self.tables.generation)
        if self._table_cache is None or self._table_cache[0] != gen:
            str_arrs = self.tables.arrays()
            # (host array, vocab axis): the vocab axis is padded to a
            # stable bucket and extended by delta uploads
            host: Dict[str, Tuple[np.ndarray, int]] = {
                "pat_member": (np.asarray(self.patterns.member), 1),
                "pat_capture": (np.asarray(self.patterns.capture), 1),
            }
            for name, tab in str_arrs.items():
                host[name] = (np.asarray(tab), 0)
            # fused transposed copies: a TPU gather op costs ~10ms
            # regardless of width, so the sweep gathers every column in
            # a handful of [V, T] row-gathers instead of one op per
            # pattern/table (the transpose is host-side; device bool
            # transposes are themselves ~100ms-class)
            fused_cols: Dict[str, Dict[Any, int]] = {}
            pm = np.asarray(self.patterns.member)
            if pm.size:
                host["pat_member!T"] = (np.ascontiguousarray(pm.T), 0)
                fused_cols["pat_member"] = {
                    i: i for i in range(pm.shape[0])
                }
                pc = np.asarray(self.patterns.capture)
                host["pat_capture!T"] = (np.ascontiguousarray(pc.T), 0)
                fused_cols["pat_capture"] = {
                    i: i for i in range(pc.shape[0])
                }
            by_kind: Dict[str, List[Tuple[str, np.ndarray]]] = {}
            for name, tab in str_arrs.items():
                t = np.asarray(tab)
                kind = (
                    "vid_bool" if t.dtype == np.bool_
                    else "vid_i32" if np.issubdtype(t.dtype, np.integer)
                    else "vid_f32"
                )
                by_kind.setdefault(kind, []).append((name, t))
            for kind, items in by_kind.items():
                dt = {"vid_bool": np.bool_, "vid_i32": np.int32,
                      "vid_f32": np.float32}[kind]
                host[kind + "!T"] = (
                    np.ascontiguousarray(
                        np.stack([t for _, t in items], axis=1).astype(dt)
                    ),
                    0,
                )
                fused_cols[kind] = {
                    name: i for i, (name, _) in enumerate(items)
                }
            pending: Dict[str, Tuple[Any, np.ndarray, int, int]] = {}
            arrs = {
                k: self._stage_table(k, a, ax, pending)
                for k, (a, ax) in host.items()
            }
            if pending:
                # apply EVERY table's delta in ONE jitted call — one
                # device dispatch per batch instead of one per table
                for name, buf in self._flush_deltas(pending).items():
                    vlen, other = arrs[name]
                    arrs[name] = buf
                    self._resident[name] = (buf, vlen, other)
            for stale in set(self._resident) - set(host):
                del self._resident[stale]
            self._fused_cols = fused_cols
            self._table_cache = (gen, arrs)
        return self._table_cache[1]

    def _stage_table(self, name: str, a: np.ndarray, ax: int, pending):
        """Device-resident table with vocab-axis padding: vocab growth
        within the padded bucket ships only the new rows (queued into
        `pending` for one fused fixed-shape dynamic_update_slice — no
        recompiles, no full re-upload); structural changes (new
        patterns/tables, bucket overflow) fall back to a full padded
        upload. Returns the device buffer, or (vlen, other) when the
        result comes from the pending flush."""
        vlen = a.shape[ax]
        other = a.shape[:ax] + a.shape[ax + 1:]
        ent = self._resident.get(name)
        if ent is not None:
            buf, fill, other0 = ent
            cap = buf.shape[ax]
            if (
                other0 == other
                and str(buf.dtype) == str(a.dtype)
                and fill <= vlen
            ):
                if fill == vlen:
                    return buf
                dl = vlen - fill
                dpad = -(-dl // self._DELTA_ROWS) * self._DELTA_ROWS
                if fill + dpad <= cap:
                    sl = [slice(None)] * a.ndim
                    sl[ax] = slice(fill, vlen)
                    delta = a[tuple(sl)]
                    if dpad != dl:
                        pad_shape = list(delta.shape)
                        pad_shape[ax] = dpad - dl
                        delta = np.concatenate(
                            [delta, np.zeros(pad_shape, a.dtype)], axis=ax
                        )
                    pending[name] = (buf, delta, fill, ax)
                    return (vlen, other)
        cap = _pad_len(vlen)
        pad_shape = list(a.shape)
        pad_shape[ax] = cap - vlen
        padded = np.concatenate(
            [a, np.zeros(pad_shape, a.dtype)], axis=ax
        ) if cap != vlen else a
        buf = self._put(padded)
        self._resident[name] = (buf, vlen, other)
        return buf

    def _flush_deltas(self, pending) -> Dict[str, Any]:
        names = sorted(pending)
        key = (
            "tabdelta",
            tuple(
                (
                    n,
                    pending[n][0].shape,
                    str(pending[n][0].dtype),
                    pending[n][1].shape,
                    pending[n][3],
                )
                for n in names
            ),
        )
        ent = self._jit_cache.get(key)
        if ent is None:
            axes = {n: pending[n][3] for n in names}

            def upd(bufs, deltas, offs):
                out = {}
                for n in names:
                    b = bufs[n]
                    starts = [jnp.int32(0)] * b.ndim
                    starts[axes[n]] = offs[n]
                    out[n] = jax.lax.dynamic_update_slice(
                        b, deltas[n].astype(b.dtype), tuple(starts)
                    )
                return out

            ent = self._jit_cache[key] = [upd, jax.jit(upd)]
        return ent[1](
            {n: pending[n][0] for n in names},
            {n: jnp.asarray(pending[n][1]) for n in names},
            {n: jnp.int32(pending[n][2]) for n in names},
        )

    # -- staged sparse dispatch ---------------------------------------------

    def stage_policy(
        self,
        programs: Sequence[Optional[Program]],
        ms: Dict[str, np.ndarray],
    ) -> StagedPolicy:
        c = next(iter(ms.values())).shape[0]
        c_mult = self.mesh.shape["c"] if self.mesh else 1
        c_pad = ((c + c_mult - 1) // c_mult) * c_mult

        # content-dedup the match-spec rows: the selector kernel runs over
        # the U distinct rows; a [C_pad] gather rebuilds the full matrix
        ms_np = {k: np.asarray(v) for k, v in ms.items()}
        uniq: Dict[bytes, int] = {}
        reps: List[int] = []
        spec_map = np.empty((c_pad,), np.int32)
        ms_keys = sorted(ms_np)
        for i in range(c):
            sig = b"|".join(ms_np[k][i].tobytes() for k in ms_keys)
            j = uniq.get(sig)
            if j is None:
                j = uniq[sig] = len(reps)
                reps.append(i)
            spec_map[i] = j
        u = len(reps)
        spec_map[c:] = u  # padded constraints -> the match-nothing row
        rep_idx = np.asarray(reps, np.int64)
        ms_dev = {}
        for k, v in ms_np.items():
            null_row = np.full((1,) + v.shape[1:], _ms_fill(k), v.dtype)
            ms_dev[k] = self._put(
                np.concatenate([v[rep_idx], null_row], axis=0)
            )  # [U+1, ...] replicated — small after dedup

        compiled = [p for p in programs if p is not None]
        prog_rows = []
        row = 0
        for p in programs:
            prog_rows.append(row if p is not None else -1)
            row += p is not None
        compiled_mask = np.zeros((c_pad,), bool)
        compiled_mask[: len(programs)] = [p is not None for p in programs]
        groups: Dict[Tuple, Dict[str, Any]] = {}
        for ci, p in enumerate(programs):
            if p is None:
                continue
            gkey = (
                p.signature,
                tuple(sorted((k, v.shape) for k, v in p.consts.items())),
            )
            grp = groups.setdefault(
                gkey,
                {"expr": p.expr, "rows": [], "consts": [], "cmap": [],
                 "cuniq": {}},
            )
            grp["rows"].append(ci)  # constraint-row index
            # dedup identical const sets within the group (constraints of
            # one template frequently share parameters)
            csig = b"|".join(
                k.encode() + b"=" + np.asarray(p.consts[k]).tobytes()
                for k in sorted(p.consts)
            )
            cj = grp["cuniq"].get(csig)
            if cj is None:
                cj = grp["cuniq"][csig] = len(grp["consts"])
                grp["consts"].append(p.consts)
            grp["cmap"].append(cj)
        group_list = list(groups.values())
        stacked_consts = [
            {
                k: self._put(np.stack([cd[k] for cd in grp["consts"]]))
                for k in grp["consts"][0]
            }
            for grp in group_list
        ]
        key = (
            tuple(groups),
            tuple(tuple(grp["rows"]) for grp in group_list),
            tuple(tuple(grp["cmap"]) for grp in group_list),
            c,
            c_pad,
            u,
            id(self.mesh),
        )
        return StagedPolicy(
            ms_dev=ms_dev,
            spec_map=self._put(spec_map, "c"),
            n_specs=u,
            group_exprs=[grp["expr"] for grp in group_list],
            group_rows=[list(grp["rows"]) for grp in group_list],
            group_cmaps=[list(grp["cmap"]) for grp in group_list],
            stacked_consts=stacked_consts,
            compiled_mask=self._put(compiled_mask, "c"),
            prog_rows=prog_rows,
            c=c,
            c_pad=c_pad,
            key=key,
        )

    def stage_corpus_stacked(
        self,
        chunks: Sequence[Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray],
                               np.ndarray, int]],
        ov: Optional[Dict[str, Any]] = None,
        v_base: int = 0,
    ) -> StackedCorpus:
        """Stack per-chunk (fb, tok, row_fb, n_valid) onto a leading
        chunk axis and ship to device once. All chunks must share the
        padded chunk shape.

        `ov` (ephemeral batches): {"member": [B, P] bool, "capture":
        [B, P] i32, "tabs": {name: [B]}} — the batch's vocab-overlay
        rows. Per-kind slabs are stacked here in the SAME column order
        as the resident fused tables so one col mapping serves both."""
        k = len(chunks)
        fb_dev = self._put_group(
            {
                key: np.stack([c[0][key] for c in chunks])
                for key in chunks[0][0]
            },
            None,
            "n",
        )
        tok_dev = self._put_group(
            {
                key: np.stack([c[1][key] for c in chunks])
                for key in chunks[0][1]
            },
            None,
            "n",
        )
        chunk = tok_dev["spath"].shape[1]
        row_fb = np.zeros((k, chunk), bool)
        for i, c in enumerate(chunks):
            row_fb[i, : len(c[2])] = c[2]
        n_valids = [c[3] for c in chunks]
        ov_dev = None
        ov_key: Tuple = ()
        if ov is not None:
            self._tables_device()  # ensure _fused_cols is current
            ov_host: Dict[str, np.ndarray] = {
                "member": np.asarray(ov["member"]),
                "capture": np.asarray(ov["capture"]),
            }
            b_pad = ov["member"].shape[0]
            tabs = ov.get("tabs") or {}
            for kind, cols in self._fused_cols.items():
                if kind in ("pat_member", "pat_capture"):
                    continue
                dt = {"vid_bool": np.bool_, "vid_i32": np.int32,
                      "vid_f32": np.float32}[kind]
                slab = np.zeros((b_pad, len(cols)), dt)
                for name, col in cols.items():
                    t = tabs.get(name)
                    if t is not None:
                        slab[:, col] = t.astype(dt)
                ov_host[kind] = slab
            ov_dev = self._put_group(ov_host)
            ov_key = (b_pad, tuple(sorted(ov_dev)))
        return StackedCorpus(
            fb_dev=fb_dev,
            tok_dev=tok_dev,
            row_fb=self._put(row_fb, None, "n"),
            n_valid=self._put(np.asarray(n_valids, np.int32)),
            n_valids=n_valids,
            k=k,
            chunk=chunk,
            key=(
                k,
                chunk,
                tok_dev["spath"].shape,
                fb_dev["group_id"].shape,
                ov_key,
            ),
            row_dev={},
            ov_dev=ov_dev,
            v_base=v_base,
        )

    def stage_row_feats(
        self, corpus: StackedCorpus, feats: Dict[str, np.ndarray],
        volatile: Sequence[str] = (),
    ) -> None:
        """Ship per-row feature bits ([N] bool each) to device as
        [K, chunk] planes alongside the stacked corpus. Names already
        staged are skipped (invdup bits are per-corpus-constant) unless
        listed in `volatile` — external-data bits track the live
        response cache, so a persistent audit corpus restages them
        every dispatch."""
        for name, arr in feats.items():
            if name in corpus.row_dev and name not in volatile:
                continue
            plane = np.zeros((corpus.k, corpus.chunk), bool)
            flat = np.asarray(arr, bool)
            for ci in range(corpus.k):
                start = ci * corpus.chunk
                end = min(start + corpus.chunk, flat.shape[0])
                if end > start:
                    plane[ci, : end - start] = flat[start:end]
            corpus.row_dev[name] = self._put(plane, None, "n")

    def dispatch_need_all(
        self,
        policy: StagedPolicy,
        corpus: StackedCorpus,
        g: int,
        r_cap: int = 1024,
        require_compiled: bool = False,
        args=None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Whole-corpus sweep in ONE device execution + ONE fetch.

        lax.map runs the per-chunk need computation (match x programs x
        hot-row compaction — see dispatch_need) over the stacked chunk
        axis; outputs come back stacked: packed [K, C_pad*R/8] uint8,
        hot [K, R] int32, n_hot [K], compiled/interp pair stats [K].
        Chunks whose n_hot exceeds r_cap are re-dispatched individually
        by the caller (rare: violating rows are sparse in steady state).

        require_compiled=True raises ColdKernel instead of compiling
        when this (policy, shape-bucket) has no jit entry yet — the
        serve-while-compiling admission path uses it so a novel batch
        bucket serves on the interpreter rather than stalling every
        in-flight request on an inline XLA compile.

        `args` are need_all_args(policy, corpus) staged by the caller;
        None stages them here.
        """
        r_cap = min(r_cap, corpus.chunk)
        row_dev = corpus.row_dev or {}
        key = (
            "need_all", policy.key, corpus.key, g, r_cap,
            tuple(sorted(row_dev)),
        )
        entry = self._jit_cache.get(key)
        if entry is None and require_compiled:
            self._note_cache("need_all", "cold")
            raise ColdKernel(f"no compiled entry for {key[:3]}")
        was_miss = entry is None
        self._note_cache("need_all", "miss" if was_miss else "hit")
        if entry is None:
            fn = self.need_all_fn(policy, g, r_cap)
            entry = [fn, jax.jit(fn)]
            self._jit_cache[key] = entry
        t_call = time.perf_counter()
        if args is None:
            args = self.need_all_args(policy, corpus)
        out = entry[1](*args)
        if was_miss:
            self._note_compile("need_all", time.perf_counter() - t_call)
        buf = np.asarray(out)  # ONE transfer for the whole sweep
        # unpack (see run_all): [pwords | hot | n_hot | sc | si]
        r_eff = min(r_cap, corpus.chunk)
        p8 = -(-policy.c_pad * r_eff // 8)
        w4 = -(-p8 // 4)
        packed = (
            np.ascontiguousarray(buf[:, :w4])
            .astype("<u4")
            .view(np.uint8)
            .reshape(corpus.k, -1)[:, :p8]
        )
        hot = buf[:, w4:w4 + r_eff]
        n_hot = buf[:, w4 + r_eff]
        sc = buf[:, w4 + r_eff + 1]
        si = buf[:, w4 + r_eff + 2]
        return packed, hot, n_hot, sc, si

    def need_all_fn(self, policy: StagedPolicy, g, r_cap: int):
        """The un-jitted whole-corpus sweep dispatch_need_all runs (also
        what tests/test_tpu_compile.py compiles for a described chip)."""
        need_chunk = self._need_chunk_fn(policy, g, r_cap)

        def run_all(ms_in, spec_map, fb_in, tok_in, tabs_in,
                    consts_in, compiled_mask, row_fb, n_valid,
                    row_in, ov_in, vb):
            def body(xs):
                fb_c, tok_c, rf_c, nv_c, row_c = xs
                return need_chunk(
                    ms_in, spec_map, fb_c, tok_c, tabs_in,
                    consts_in, compiled_mask, rf_c, nv_c, row_c,
                    ov_in=ov_in, v_base=vb,
                )

            packed, hot, n_hot, sc, si = jax.lax.map(
                body, (fb_in, tok_in, row_fb, n_valid, row_in)
            )
            # fuse the five outputs into ONE int32 buffer: a
            # device->host fetch pays a round trip per ARRAY, so five
            # leaves would cost five.
            # Bytes pack into words with EXPLICIT little-endian
            # shifts (bitcast_convert_type's byte assembly is
            # platform-defined; the host unpack views '<u4')
            k_chunks, p8 = packed.shape
            pad = (-p8) % 4
            pw = (
                jnp.pad(packed, ((0, 0), (0, pad)))
                .reshape(k_chunks, (p8 + pad) // 4, 4)
                .astype(jnp.int32)
            )
            pwords = (
                pw[..., 0]
                | (pw[..., 1] << 8)
                | (pw[..., 2] << 16)
                | (pw[..., 3] << 24)
            )
            return jnp.concatenate(
                [
                    pwords,
                    hot,
                    n_hot[:, None],
                    sc[:, None],
                    si[:, None],
                ],
                axis=1,
            )

        return run_all

    def need_all_args(self, policy: StagedPolicy, corpus: StackedCorpus):
        """need_all_fn's operands: the staged policy and corpus."""
        return (
            policy.ms_dev,
            policy.spec_map,
            corpus.fb_dev,
            corpus.tok_dev,
            self._tables_device(),
            policy.stacked_consts,
            policy.compiled_mask,
            corpus.row_fb,
            corpus.n_valid,
            corpus.row_dev or {},
            corpus.ov_dev or {},
            jnp.int32(corpus.v_base),
        )

    def _need_chunk_fn(self, policy: StagedPolicy, g, r_cap: int):
        """The shared per-chunk need computation (trace-time closure
        over the policy's program groups)."""
        g0_, g1_ = _g01(g)
        group_exprs = policy.group_exprs
        group_rows = policy.group_rows
        group_cmaps = policy.group_cmaps

        def need_chunk(ms_in, spec_map, fb_in, tok_in, tabs_in,
                       consts_in, compiled_mask, row_fb, n_valid,
                       row_in=None, ov_in=None, v_base=None):
            from ..engine.exprs import EvalCtx

            # [U+1, N] over distinct specs, gathered back to [C_pad, N]
            match_u = match_matrix(ms_in, fb_in)
            match = match_u[spec_map]
            str_tabs = {
                k: v
                for k, v in tabs_in.items()
                if k not in ("pat_member", "pat_capture")
                and not k.endswith("!T")
            }
            has_ov = bool(ov_in)

            def two_level(base_tab, ov_tab, ids):
                """Gather rows by id: base table below v_base, the
                batch's overlay block above (ephemeral vocab ids)."""
                rows = base_tab.shape[0]
                base = base_tab[jnp.clip(ids, 0, rows - 1)]
                if not has_ov or ov_tab is None:
                    return base
                loc = ids - v_base
                b = ov_tab.shape[0]
                ov = ov_tab[jnp.clip(loc, 0, b - 1)]
                return jnp.where((loc >= 0)[..., None], ov, base)

            # fused pre-gathers, ONCE per chunk in the outer trace and
            # shared by every group and vmap lane (each expression node
            # slices its column); XLA DCEs any slab no node touches
            slabs = {}
            if "pat_member!T" in tabs_in:
                safe_sp = jnp.maximum(tok_in["spath"], 0)
                slabs["pat_member"] = two_level(
                    tabs_in["pat_member!T"],
                    ov_in.get("member") if has_ov else None,
                    safe_sp,
                )
                slabs["pat_capture"] = two_level(
                    tabs_in["pat_capture!T"],
                    ov_in.get("capture") if has_ov else None,
                    safe_sp,
                )
            safe_vid = jnp.maximum(tok_in["vid"], 0)
            for kind in ("vid_bool", "vid_i32", "vid_f32"):
                if kind + "!T" in tabs_in:
                    slabs[kind] = two_level(
                        tabs_in[kind + "!T"],
                        ov_in.get(kind) if has_ov else None,
                        safe_vid,
                    )
            slab_cols = self._fused_cols
            ov_cols = None
            if has_ov:
                ov_cols = {
                    name: (kind, col)
                    for kind, cols in self._fused_cols.items()
                    if kind not in ("pat_member", "pat_capture")
                    for name, col in cols.items()
                }
            viol = jnp.zeros(match.shape, bool)
            for expr, grows, cmap, consts_k in zip(
                group_exprs, group_rows, group_cmaps, consts_in
            ):

                def eval_one(consts):
                    ctx = EvalCtx(
                        np=jnp,
                        tok=tok_in,
                        pat_member=tabs_in["pat_member"],
                        pat_capture=tabs_in["pat_capture"],
                        str_tables=str_tabs,
                        consts=consts,
                        g0=g0_,
                        g1=g1_,
                        slabs=slabs,
                        slab_cols=slab_cols,
                        row=row_in,
                        v_base=v_base if has_ov else None,
                        ov_slabs=ov_in if has_ov else None,
                        ov_cols=ov_cols,
                    )
                    return expr.emit(ctx).astype(jnp.int32)

                if consts_k:
                    # [Ku, N] over distinct const sets, gathered out
                    # to the group's constraint rows
                    out_u = jax.vmap(eval_one)(consts_k) > 0
                    out_k = out_u[jnp.asarray(cmap)]
                else:
                    one = eval_one({}) > 0
                    out_k = jnp.broadcast_to(
                        one, (len(grows),) + one.shape
                    )
                viol = viol.at[jnp.asarray(grows)].set(out_k)

            valid_n = jnp.arange(match.shape[1]) < n_valid
            fallback = (~compiled_mask[:, None]) | row_fb[None, :]
            need = match & (viol | fallback) & valid_n[None, :]
            stat_c = jnp.sum(
                match & compiled_mask[:, None] & ~row_fb[None, :]
                & valid_n[None, :]
            )
            stat_i = jnp.sum(match & fallback & valid_n[None, :])
            # hot-row compaction: nonzero over [N] is cheap; the
            # full-matrix nonzero/transpose is not
            rowany = need.any(axis=0)  # [N]
            n_hot = rowany.sum().astype(jnp.int32)
            hot = jnp.nonzero(rowany, size=r_cap, fill_value=-1)[0]
            sub = need[:, jnp.maximum(hot, 0)] & (hot >= 0)[None, :]
            return (
                jnp.packbits(sub.reshape(-1)),  # c-major over R cols
                hot.astype(jnp.int32),
                n_hot,
                stat_c.astype(jnp.int32),
                stat_i.astype(jnp.int32),
            )

        return need_chunk

    def dispatch_need(
        self,
        policy: StagedPolicy,
        batch: StagedBatch,
        g: int,
        block: bool = True,
        r_cap: int = 4096,
        row_in: Optional[Dict[str, Any]] = None,
        ov_in: Optional[Dict[str, Any]] = None,
        v_base: int = 0,
        require_compiled: bool = False,
    ) -> Tuple[Any, Any, Any, Any, Any]:
        """-> (packed hot-row need bits [C_pad x R / 8] uint8 c-major,
        hot row ids [R] int32, n_hot, compiled_pairs, interp_pairs) for
        one staged chunk.

        The need matrix is compacted on device to the rows that have any
        needing pair (violating reviews are sparse in steady state):
        a [N]-sized nonzero picks the hot rows, a gather extracts their
        [C_pad, R] need columns, and only that bitmap leaves the device
        (~C_pad*R/8 bytes — the full [C_pad, N] bitmap is a multi-MB
        transfer and device-side full nonzero costs a ~150ms scatter
        pass plus a ~400ms transpose per chunk on v5e). `n_hot` may
        exceed r_cap: callers re-dispatch with a larger cap
        (TpuDriver._need_pairs does). Stats count matched pairs on the
        compiled vs interpreter routes (valid rows only).

        With block=False the outputs come back as device arrays without
        synchronizing — callers dispatch every chunk first, then resolve
        with one device_get each, so chunk k+1's compute overlaps chunk
        k's host decode. `n_valid` rides as a runtime scalar: any chunk
        occupancy reuses one compiled program per (policy, shape-bucket,
        r_cap).
        """
        n_pad = batch.tok_dev["spath"].shape[0]
        r_cap = min(r_cap, n_pad)
        row_in = row_in or {}
        ov_in = ov_in or {}
        key = ("need", policy.key, batch.key, g, r_cap,
               tuple(sorted(row_in)), tuple(sorted(ov_in)))
        entry = self._jit_cache.get(key)
        if entry is None and require_compiled:
            self._note_cache("need", "cold")
            raise ColdKernel(f"no compiled entry for {key[:3]}")
        was_miss = entry is None
        self._note_cache("need", "miss" if was_miss else "hit")
        if entry is None:
            run_need = self._need_chunk_fn(policy, g, r_cap)
            entry = [run_need, jax.jit(run_need)]
            self._jit_cache[key] = entry
        tabs = self._tables_device()
        t_call = time.perf_counter()
        out = entry[1](
            policy.ms_dev,
            policy.spec_map,
            batch.fb_dev,
            batch.tok_dev,
            tabs,
            policy.stacked_consts,
            policy.compiled_mask,
            batch.row_fb,
            jnp.int32(batch.n_valid),
            row_in,
            ov_in,
            jnp.int32(v_base),
        )
        if was_miss:
            self._note_compile("need", time.perf_counter() - t_call)
        if not block:
            return out
        packed, hot, n_hot, stat_c, stat_i = _get_overlapped(out)
        return packed, hot, int(n_hot), int(stat_c), int(stat_i)

    # -- dispatch ------------------------------------------------------------

    def prepare(
        self,
        programs: Sequence[Optional[Program]],
        ms: Dict[str, np.ndarray],
        fb: Dict[str, np.ndarray],
        tok: Dict[str, np.ndarray],
        g: int,
    ):
        """Build (fn, args, (c, n)) for one dispatch: `fn(*args)` returns
        (match, counts, totals) padded; fn is an un-jitted closure so
        callers (the harness entry point) may compile-check it themselves.
        """
        c = next(iter(ms.values())).shape[0]
        n = next(iter(fb.values())).shape[0]
        compiled = [p for p in programs if p is not None]
        prog_c_rows = [i for i, p in enumerate(programs) if p is not None]

        # Group programs by structural signature (same template control
        # flow + const shapes): one traced subgraph per group, vmapped
        # over the stacked const tensors. A 500-constraint population of
        # ~8 templates traces ~8 subgraphs, not 500 — constraints differ
        # only in the consts they pass (engine/programs.py docstring).
        groups: Dict[Tuple, Dict[str, Any]] = {}
        for out_row, p in enumerate(compiled):
            gkey = (
                p.signature,
                tuple(sorted((k, v.shape) for k, v in p.consts.items())),
            )
            grp = groups.setdefault(
                gkey, {"expr": p.expr, "rows": [], "consts": []}
            )
            grp["rows"].append(out_row)
            grp["consts"].append(p.consts)

        c_mult = self.mesh.shape["c"] if self.mesh else 1
        n_mult = self.mesh.shape["n"] if self.mesh else 1

        ms_dev = {
            k: self._put(_pad_axis(np.asarray(v), 0, c_mult, _ms_fill(k)), "c")
            for k, v in ms.items()
        }
        fb_dev = {
            k: self._put(_pad_axis(np.asarray(v), 0, n_mult, _fb_fill(k)), "n")
            for k, v in fb.items()
        }
        tok_dev = {
            k: self._put(
                _pad_axis(np.asarray(v), 0, n_mult, 0.0 if k == "vnum" else -1),
                "n",
            )
            for k, v in tok.items()
        }
        tabs = self._tables_device()
        # per-group stacked consts: dict name -> [K, ...] device array
        group_list = list(groups.values())
        stacked_consts = [
            {
                k: self._put(np.stack([cd[k] for cd in grp["consts"]]))
                for k in grp["consts"][0]
            }
            for grp in group_list
        ]

        key = (
            tuple(gk for gk in groups),
            tuple(tuple(grp["rows"]) for grp in group_list),
            tuple(prog_c_rows),
            g,
            n,
            tok_dev["spath"].shape,
            fb_dev["group_id"].shape,
            ms_dev["kind_rows"].shape,
            id(self.mesh),
        )
        entry = self._jit_cache.get(key)
        fn = entry[0] if entry is not None else None
        if fn is None:
            n_compiled = len(compiled)
            group_exprs = [grp["expr"] for grp in group_list]
            group_rows = [list(grp["rows"]) for grp in group_list]
            rows = list(prog_c_rows)

            def run_fused(ms_in, fb_in, tok_in, tabs_in, consts_in):
                from ..engine.exprs import EvalCtx

                match = match_matrix(ms_in, fb_in)  # [C, N]
                str_tabs = {
                    k: v
                    for k, v in tabs_in.items()
                    if k not in ("pat_member", "pat_capture")
                }
                if group_exprs:
                    n_pad = tok_in["spath"].shape[0]
                    counts = jnp.zeros((n_compiled, n_pad), jnp.int32)
                    for expr, grows, consts_k in zip(
                        group_exprs, group_rows, consts_in
                    ):

                        def eval_one(consts):
                            g0_, g1_ = _g01(g)
                            ctx = EvalCtx(
                                np=jnp,
                                tok=tok_in,
                                pat_member=tabs_in["pat_member"],
                                pat_capture=tabs_in["pat_capture"],
                                str_tables=str_tabs,
                                consts=consts,
                                g0=g0_,
                                g1=g1_,
                            )
                            return expr.emit(ctx).astype(jnp.int32)

                        if consts_k:
                            out_k = jax.vmap(eval_one)(consts_k)  # [K, N]
                        else:
                            # const-free program: every constraint in the
                            # group computes the same counts
                            one = eval_one({})
                            out_k = jnp.broadcast_to(
                                one, (len(grows),) + one.shape
                            )
                        counts = counts.at[jnp.asarray(grows)].set(out_k)
                    # scatter compiled counts back onto constraint rows so
                    # totals line up with the full constraint set
                    viol = jnp.zeros(match.shape, jnp.int32)
                    viol = viol.at[jnp.asarray(rows)].set(counts)
                else:
                    counts = None
                    viol = jnp.zeros(match.shape, jnp.int32)
                # mask padded resource rows (wildcard constraints match
                # the all-pad feature rows) before reducing
                valid_n = jnp.arange(match.shape[1]) < n
                # the one collective: per-constraint totals reduce over
                # the sharded "n" axis (GSPMD all-reduce)
                totals = jnp.sum(
                    (jnp.where(match, viol, 0) > 0) & valid_n[None, :], axis=1
                ).astype(jnp.int32)
                return match, counts, totals

            fn = run_fused
            self._jit_cache[key] = [fn, None]
        return fn, (ms_dev, fb_dev, tok_dev, tabs, stacked_consts), (c, n, key)

    def run(
        self,
        programs: Sequence[Optional[Program]],
        ms: Dict[str, np.ndarray],
        fb: Dict[str, np.ndarray],
        tok: Dict[str, np.ndarray],
        g: int,
    ) -> Tuple[np.ndarray, Optional[np.ndarray], np.ndarray]:
        """-> (match [C, N] bool, counts [Cc, N] int32 | None,
                totals [C] int32 per-constraint compiled-path violation
                totals).

        `programs` is index-aligned with the C constraint rows; None
        entries (interpreter-fallback templates) contribute no counts and
        no totals."""
        fn, args, (c, n, key) = self.prepare(programs, ms, fb, tok, g)
        entry = self._jit_cache[key]
        if entry[1] is None:
            entry[1] = jax.jit(fn)
        match_p, counts_p, totals_p = entry[1](*args)
        match = np.asarray(match_p)[:c, :n]
        counts = None if counts_p is None else np.asarray(counts_p)[:, :n]
        totals = np.asarray(totals_p)[:c]
        return match, counts, totals


def decode_need(
    packed: np.ndarray, hot: np.ndarray, c_pad: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Packed hot-row need bits -> (n_idx, c_idx) arrays sorted
    review-major (matching the interpreter driver's emit order)."""
    hot = np.asarray(hot)
    r = hot.shape[0]
    bits = np.unpackbits(np.asarray(packed))[: c_pad * r]
    c_is, j_is = np.nonzero(bits.reshape(c_pad, r))
    n_loc = hot[j_is]
    order = np.lexsort((c_is, n_loc))
    return n_loc[order], c_is[order]


def _ms_fill(key: str):
    """Pad constraint rows so they match nothing: all-pad kind selectors
    (-1 rows are invalid) and inert selector/scope fields."""
    if key in ("ns_has", "excl_has", "nssel_has", "nssel_matches_empty",
               "lab_invalid", "nssel_invalid"):
        return False
    if key == "scope":
        return 0  # SCOPE_ABSENT
    return -1


def _fb_fill(key: str):
    if key in (
        "kind_defined",
        "is_ns",
        "has_namespace",
        "obj_present",
        "old_present",
        "nssel_defined",
        "nssel_empty",
        "label_overflow",
    ):
        return False
    return -1

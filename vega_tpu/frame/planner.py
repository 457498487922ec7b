"""Frame logical -> physical compiler.

Two lowerings of the SAME optimized logical plan:

* **Device tier** — scans become lazy columnar DenseRDD sources (pruned
  columns + pushed predicates reach the parquet reader, so unneeded data
  never leaves the file); every maximal run of select/filter/with_column
  steps fuses into ONE `dense_pipeline` node, i.e. one traced SPMD shard
  program per stage; groupBy/agg lowers onto the named-op segment reduce
  (uniform monoid) or a generated traced TUPLE combiner (mixed monoids) —
  monoid selection is by aggregate NAME, never value probing; join/sort
  lower onto the device exchange kernels, with the per-exchange program
  chosen by the frame's `hint(exchange=)` or the shared exchange cost
  model (tpu/exchange_plan.py — the same planner the node-level
  `dense_exchange=auto` resolution runs).
* **Host tier** — the identical verbs over ordinary RDD lineages
  (columnar blocks until the first exchange, row tuples after), produced
  whenever the device trace rejects an expression (opaque Python UDFs,
  non-device dtypes) or a verb shape the kernels cannot take. The switch
  is SILENT — same results, different placement — preserving the
  two-tier contract. Only `tier="device"` (explicit) turns a fallback
  into an error.

Compilation is pure plan algebra + metadata reads + abstract tracing
(`jax.eval_shape`): no partition is computed, no block materialized, no
device transfer issued until an action runs (api.py). VG013 machine-
checks that property for every module in this package except api.py."""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from vega_tpu.errors import VegaError
from vega_tpu.frame import logical as L
from vega_tpu.frame import physical as P
from vega_tpu.frame.expr import _AGG_MONOID, BinOp, Col, Expr, Lit, \
    UnaryOp
from vega_tpu.frame.physical import HostFallback
from vega_tpu.tpu import spans

DEFAULT_OPTIONS = {
    "fuse": True,        # whole-stage fusion (False: one program per verb)
    "pushdown": True,    # column pruning + predicate pushdown into scans
    "tier": "auto",      # auto | device | host
    "exchange": None,    # device exchange override
                         # (auto|all_to_all|ring|staged)
    "shuffle_plan": None,  # host-tier distributed shuffle plan (pull|push)
}

# Device-lowering fallback observability: every compile that lands on the
# host tier because the device trace rejected the plan bumps the counter
# (and the profiler session's `frame_fallbacks` count) and records why.
# Tests (and the strings A/B) use it to PROVE a plan compiled to the
# device tier — "it returned the right rows" cannot distinguish the tiers,
# the counter can.
_FALLBACKS = {"count": 0, "last": None}


def fallback_count() -> int:
    """Total device->host compile fallbacks in this process."""
    return _FALLBACKS["count"]


def last_fallback() -> Optional[str]:
    """Reason string of the most recent device->host fallback."""
    return _FALLBACKS["last"]



class Compiled:
    """Physical plan handle: a lazy RDD lineage plus the metadata the
    action surface (api.py) needs to extract frame-shaped results."""

    def __init__(self, kind: str, rdd, cols: List[str],
                 out: List[Tuple[str, str]], layout: str,
                 limit: Optional[int], plan, notes: List[str],
                 dates=()):
        self.kind = kind          # "device" | "host"
        self.rdd = rdd
        self.cols = cols          # frame output columns, in order
        self.out = out            # device: (frame_name, block_name)
        self.layout = layout      # device: "block"; host: "blocks"|"rows"
        self.limit = limit
        self.plan = plan
        self.notes = notes
        self.dates = set(dates)   # device: output columns held as days

    def explain(self) -> str:
        head = f"== physical: {self.kind} tier =="
        body = L.explain_tree(self.plan)
        notes = "".join(f"\n-- {n}" for n in self.notes)
        lim = f"\n-- limit {self.limit}" if self.limit is not None else ""
        return f"{head}\n{body}{notes}{lim}"


def compile_plan(ctx, plan: L.LogicalPlan, options: dict) -> Compiled:
    """The logical plan as a lazy lineage, in the `frame_plan` span (which
    yields to the spans of what planning reads: a source's dictionary
    build, a dictionary merge, a closure's fingerprint)."""
    with spans.span("frame_plan", yields=True):
        return _compile_plan(ctx, plan, options)


def _compile_plan(ctx, plan: L.LogicalPlan, options: dict) -> Compiled:
    options = {**DEFAULT_OPTIONS, **(options or {})}
    limit = None
    while isinstance(plan, L.Limit):
        limit = plan.n if limit is None else min(limit, plan.n)
        plan = plan.child
    pushdown = bool(options["pushdown"])
    opt = L.optimize(plan, pushdown=pushdown) if pushdown else plan
    tier = options["tier"]
    notes: List[str] = []
    if tier != "host":
        try:
            return _compile_device(ctx, opt, options, limit, notes)
        except HostFallback as e:
            if tier == "device":
                raise VegaError(
                    f"tier='device' requested but the plan has no device "
                    f"lowering: {e}") from e
            _FALLBACKS["count"] += 1
            _FALLBACKS["last"] = str(e)
            # vegalint: ignore[VG013] — a tally counter, not an RDD action
            spans.count("frame_fallbacks")
            notes.append(f"host tier: {e}")
    else:
        notes.append("host tier: requested via hint")
    return _compile_host(ctx, opt, options, limit, notes)


# ---------------------------------------------------------------------------
# shared lowering helpers
# ---------------------------------------------------------------------------


def _sanitize(name: str, taken: set) -> str:
    """Frame name -> block column name: the canonical key name and the
    wide-int64 low-word suffix are reserved by the block layout."""
    bn = name
    if bn == "k" or bn.endswith(".lo") or not bn:
        bn = "c_" + bn.replace(".", "_")
    while bn in taken:
        bn += "_"
    taken.add(bn)
    return bn


def _agg_specs(node: L.GroupAgg):
    """Normalize aggregates to (block_name, input Expr, monoid) triples
    plus finalize slots: count -> sum of ones, mean -> (sum, count) pair
    divided after the exchange. Monoids come from the aggregate NAME
    (sound by construction — CLAUDE.md bans value probing)."""
    taken = {"k"}
    specs: List[tuple] = []   # (block_name, Expr, monoid)
    slots: List[tuple] = []   # ('v', i) | ('mean', i_sum, i_count)
    for a in node.aggs:
        if a.op == "count":
            specs.append((_sanitize(a.alias, taken), Lit(1), "add"))
            slots.append(("v", len(specs) - 1))
        elif a.op == "mean":
            specs.append((_sanitize(a.alias, taken), a.expr, "add"))
            i_sum = len(specs) - 1
            specs.append((_sanitize(a.alias + "__n", taken), Lit(1), "add"))
            slots.append(("mean", i_sum, len(specs) - 1))
        else:
            specs.append((_sanitize(a.alias, taken), a.expr,
                          _AGG_MONOID[a.op]))
            slots.append(("v", len(specs) - 1))
    return specs, slots


# ---------------------------------------------------------------------------
# device lowering
# ---------------------------------------------------------------------------


class _DState:
    """Device lowering cursor: the dense node built so far, the frame->
    block column mapping, and the pending (not yet flushed) narrow steps
    of the current stage."""

    def __init__(self, node, colmap: List[Tuple[str, str]],
                 dict_cols=(), date_cols=()):
        self.node = node
        self.colmap = list(colmap)
        self.steps: List[tuple] = []
        self.est_rows: Optional[int] = None  # source row estimate
        # Frame columns currently dictionary-encoded (string columns on
        # int32 codes): codes support equality/order/passthrough, never
        # arithmetic — _flush gates any computing expression over them.
        self.dict_cols = set(dict_cols)
        # Frame columns held as int32 days (dates): they pass through, and
        # compare with each other and with day literals, nothing else.
        self.date_cols = set(date_cols)


def _step_token(step) -> tuple:
    kind, payload = step
    if kind == "project":
        return ("project", tuple((nm, e.token()) for nm, e in payload))
    return ("filter", payload.token())


def _dev_broadcast(v, cap, jnp):
    arr = jnp.asarray(v)
    if arr.ndim == 0:
        return jnp.broadcast_to(arr, (cap,))
    return arr


def _flush(st: _DState, out_pairs: List[Tuple[str, Expr]], fused: bool):
    """Compile the pending stage + final projection into ONE dense
    pipeline node (or prove it identity and skip). Raises HostFallback
    when the stage does not trace."""
    import jax
    import jax.numpy as jnp

    from vega_tpu.tpu import dense_rdd as dr
    from vega_tpu.tpu import kernels

    node = st.node
    in_schema = tuple(node._schema())
    in_names = [nm for nm, _ in in_schema]
    colmap = list(st.colmap)
    steps = list(st.steps)
    out_names = [bn for bn, _e in out_pairs]
    if not steps:
        ident = dict(colmap)
        if out_names == in_names and all(
                isinstance(e, Col) and ident.get(e.name) == bn
                for bn, e in out_pairs):
            return node  # pure passthrough: nothing to compile
    from vega_tpu.frame.expr import evaluate

    # Dictionary (string) columns through the stage: codes only ever
    # PASS THROUGH (bare Col) — any computing expression over one would
    # run arithmetic on dictionary codes (meaningless values), so it
    # lowers on the host tier instead. Filters whose predicate avoids
    # dict columns are fine: compaction moves code rows untouched.
    # `origin` tracks which parent block column each live frame column
    # is a pure passthrough of; surviving passthroughs become the
    # pipeline's _dict_renames so Block.dicts follows the data.
    dict_live = set(st.dict_cols)
    origin = {fn: bn for fn, bn in colmap}

    def _refs(e) -> set:
        out: set = set()
        e.references(out)
        return out

    for kind, payload in steps:
        if kind == "project":
            for nm, e in payload:
                if not isinstance(e, Col) and _refs(e) & dict_live:
                    raise HostFallback(
                        f"expression over string column(s) "
                        f"{sorted(_refs(e) & dict_live)} computes on "
                        "dictionary codes; host tier evaluates it")
            origin = {nm: (origin.get(e.name)
                           if isinstance(e, Col) else None)
                      for nm, e in payload}
            dict_live = {nm for nm, e in payload
                         if isinstance(e, Col) and e.name in dict_live}
        else:  # filter
            if _refs(payload) & dict_live:
                raise HostFallback(
                    f"filter over string column(s) "
                    f"{sorted(_refs(payload) & dict_live)} compares "
                    "dictionary codes; host tier evaluates it")
    dict_renames = {}
    for bn, e in out_pairs:
        if isinstance(e, Col):
            src = origin.get(e.name)
            if src is not None:
                dict_renames[bn] = src
        elif _refs(e) & dict_live:
            raise HostFallback(
                f"expression over string column(s) "
                f"{sorted(_refs(e) & dict_live)} computes on "
                "dictionary codes; host tier evaluates it")
    # Date columns (int32 days) through the stage: they pass through, and
    # compare with each other and with day literals, which lower to the
    # same days; anything else over a date computes on the host tier,
    # where a date stays a date.
    date_live = set(st.date_cols)

    def _check_days(e) -> None:
        if not isinstance(e, Col) and not _dates_compared_only(e, date_live):
            raise HostFallback(
                f"expression {e!r} computes on a date column or a day "
                "literal other than by comparing days; host tier "
                "evaluates it")

    for kind, payload in steps:
        if kind == "project":
            for _nm, e in payload:
                _check_days(e)
            date_live = {nm for nm, e in payload
                         if isinstance(e, Col) and e.name in date_live}
        else:
            _check_days(payload)
    for _bn, e in out_pairs:
        _check_days(e)
    dev_steps = [(kind, [(nm, _lower_days(e)) for nm, e in payload]
                  if kind == "project" else _lower_days(payload))
                 for kind, payload in steps]
    dev_out = [(bn, _lower_days(e)) for bn, e in out_pairs]

    def cols_fn(cols, count):
        cap = cols[in_names[0]].shape[0]
        env = {fn: cols[bn] for fn, bn in colmap}
        for kind, payload in dev_steps:
            if kind == "project":
                env = {nm: _dev_broadcast(evaluate(e, env), cap, jnp)
                       for nm, e in payload}
            else:  # filter
                keep = _dev_broadcast(evaluate(payload, env), cap, jnp)
                keep = keep.astype(jnp.bool_) \
                    & kernels.valid_mask(cap, count)
                env, count = kernels.compact(env, keep, cap)
        out = {bn: _dev_broadcast(evaluate(e, env), cap, jnp)
               for bn, e in dev_out}
        return out, count

    try:
        structs = [jax.ShapeDtypeStruct((8,), dt) for _nm, dt in in_schema]
        count_s = jax.ShapeDtypeStruct((), jnp.int32)

        def wrap(count, *arrays):
            out, c = cols_fn(dict(zip(in_names, arrays)), count)
            return tuple(out[bn] for bn in out_names) + (c,)

        shapes = jax.eval_shape(wrap, count_s, *structs)
    except HostFallback:
        raise
    except Exception as e:  # noqa: BLE001 — any trace failure: host tier
        raise HostFallback(f"stage does not trace: {e}") from e
    out_schema = tuple(
        (bn, s.dtype) for bn, s in zip(out_names, shapes))
    token = ("frame_stage", tuple(colmap),
             tuple(_step_token(s) for s in steps),
             tuple((bn, e.token()) for bn, e in out_pairs))
    return dr.dense_pipeline(node, cols_fn, out_schema, token, fused=fused,
                             dict_renames=dict_renames)


def _passed_through(st: _DState, out_cols: List[str], live) -> set:
    """The columns of `live` that are still what they were AFTER the
    pending steps: a column survives a project only as a bare Col
    passthrough (anything else over a dictionary or a date already raises
    in _flush), and filters never change column identity."""
    live = set(live)
    for kind, payload in st.steps:
        if kind == "project":
            live = {nm for nm, e in payload
                    if isinstance(e, Col) and e.name in live}
    return {c for c in out_cols if c in live}


def _dicts_after(st: _DState, out_cols: List[str]) -> set:
    """Frame columns still dictionary-encoded after the pending steps."""
    return _passed_through(st, out_cols, st.dict_cols)


def _dates_after(st: _DState, out_cols: List[str]) -> set:
    """Frame columns still int32 days after the pending steps."""
    return _passed_through(st, out_cols, st.date_cols)


_COMPARE = ("==", "!=", "<", "<=", ">", ">=")


def _is_day(value) -> bool:
    import datetime

    if isinstance(value, np.datetime64):
        return np.datetime_data(value.dtype)[0] == "D"
    return isinstance(value, datetime.date) \
        and not isinstance(value, datetime.datetime)


def _dates_compared_only(e: Expr, dates: set) -> bool:
    """True when every date column and day literal in `e` is an operand of
    a comparison of two such: what int32 days answer as dates do."""

    def day(x) -> bool:
        return (isinstance(x, Col) and x.name in dates) \
            or (isinstance(x, Lit) and _is_day(x.value))

    if isinstance(e, BinOp):
        if e.op in _COMPARE and (day(e.left) or day(e.right)):
            return day(e.left) and day(e.right)
        return _dates_compared_only(e.left, dates) \
            and _dates_compared_only(e.right, dates)
    if isinstance(e, UnaryOp):
        return _dates_compared_only(e.operand, dates)
    if isinstance(e, Lit):
        return not _is_day(e.value)
    refs: set = set()
    e.references(refs)
    return not refs & dates


def _lower_days(e: Expr) -> Expr:
    """`e` with every day literal as its int32 days (P.date_literal)."""
    if isinstance(e, Lit) and _is_day(e.value):
        return Lit(P.date_literal(e.value))
    if isinstance(e, BinOp):
        return BinOp(e.op, _lower_days(e.left), _lower_days(e.right))
    if isinstance(e, UnaryOp):
        return UnaryOp(e.op, _lower_days(e.operand))
    return e


def _key_dtype(node, allowed) -> None:
    import jax.numpy as jnp

    dt = jnp.dtype(dict(node._schema())["k"])
    if dt not in tuple(jnp.dtype(a) for a in allowed):
        raise HostFallback(
            f"device exchange key must be {allowed}, got {dt}")


def _pick_exchange(ctx, options: dict, st: _DState, width: int,
                   notes: List[str]) -> Optional[str]:
    """Per-exchange plugin policy: an explicit hint wins; otherwise
    consult the SAME cost model the node-level dense_exchange=auto
    resolution runs (tpu/exchange_plan.py — one source of truth, not a
    drifting copy of its size heuristic): when the model predicts the
    one-shot footprint busts the HBM budget at this exchange's estimated
    rows, opt the exchange into planner resolution explicitly and note
    the predicted program. Decided from source metadata, never by
    materializing (pure plan algebra — VG013)."""
    if options["exchange"] is not None:
        return options["exchange"]
    if st.est_rows is None:
        return None
    from vega_tpu.env import Env
    from vega_tpu.tpu import exchange_plan, mesh as mesh_lib

    if getattr(Env.get().conf, "dense_exchange", "auto") != "auto":
        # A globally forced program (the A/B legs, TPU tuning runs) must
        # reach the launch untouched: returning "auto" here would stamp
        # a node-level mode that beats the global config.
        return None
    budget = getattr(Env.get().conf, "dense_hbm_budget", 4 << 30)
    # Device lowering already built mesh-bound source nodes, so the
    # default mesh is resolved by the time any exchange is picked.
    plan = exchange_plan.predict_for_rows(
        st.est_rows, 4 * max(width, 1), mesh_lib.default_mesh().size,
        budget)
    if plan.program != "all_to_all":
        notes.append(
            f"exchange=auto (planner predicts {plan.program}, est peak "
            f"{plan.est_peak_bytes >> 20} MiB vs budget "
            f"{budget >> 20} MiB)")
    # Never stamp a node-level mode for the default path: the launch
    # reads the global config, so dense_exchange stays runtime-flippable
    # (a compiled frame re-executed under a later global force runs the
    # forced program, not a pinned "auto").
    return None


def _lower_device(ctx, plan: L.LogicalPlan, options: dict,
                  notes: List[str]) -> _DState:
    fused = bool(options["fuse"])
    if isinstance(plan, L.ColumnsScan):
        taken: set = set()
        names = [(fn, _sanitize(fn, taken)) for fn in plan.data]
        node = P.make_columns_source(ctx, plan.data, names)
        st = _DState(node, names,
                     dict_cols=getattr(node, "_frame_dict_cols", ()),
                     date_cols=getattr(node, "_frame_date_cols", ()))
        st.est_rows = len(next(iter(plan.data.values()))) if plan.data \
            else 0
        return st
    if isinstance(plan, L.ParquetScan):
        from vega_tpu.io.readers import parquet_schema

        cols = plan.columns()
        dtypes = parquet_schema(plan.path)
        missing = [c for c in cols if c not in dtypes]
        if missing:
            raise VegaError(
                f"unknown column(s) {missing} — parquet file "
                f"{plan.path!r} has {sorted(dtypes)}")
        taken = set()
        names = [(fn, _sanitize(fn, taken)) for fn in cols]
        node = P.make_parquet_source(ctx, plan.path, cols, plan.predicate,
                                     names, dtypes)
        st = _DState(node, names,
                     dict_cols=getattr(node, "_frame_dict_cols", ()),
                     date_cols=getattr(node, "_frame_date_cols", ()))
        try:
            from vega_tpu.io.readers import parquet_num_rows

            st.est_rows = parquet_num_rows(plan.path)
        except Exception:  # noqa: BLE001 — estimate only
            st.est_rows = None
        return st
    if isinstance(plan, L.CachedScan):
        return plan.cache.device(ctx, options, notes)
    if isinstance(plan, L.Project):
        st = _lower_device(ctx, plan.child, options, notes)
        st.steps.append(("project", list(plan.outputs)))
        if not fused:
            st = _unfused_break(st, plan.columns(), options)
        return st
    if isinstance(plan, L.Filter):
        st = _lower_device(ctx, plan.child, options, notes)
        st.steps.append(("filter", plan.predicate))
        if not fused:
            st = _unfused_break(st, plan.columns(), options)
        return st
    if isinstance(plan, L.GroupAgg):
        st = _lower_device(ctx, plan.child, options, notes)
        specs, slots = _agg_specs(plan)
        live = _dicts_after(st, plan.child.columns())
        dlive = _dates_after(st, plan.child.columns())
        ops = [m for _bn, _e, m in specs]
        dict_specs = set()
        date_specs = set()
        for bn, e, m in specs:
            refs: set = set()
            e.references(refs)
            if refs & dlive:
                # min/max of a date column is a date; any other aggregate
                # over int32 days has no date meaning on either tier
                if m not in ("min", "max") or not isinstance(e, Col):
                    raise HostFallback(
                        f"aggregate '{m}' over date column(s) "
                        f"{sorted(refs & dlive)}; host tier aggregates it")
                date_specs.add(bn)
            if refs & live:
                # Rank codes make min/max of a string column sound on
                # device; every other monoid would fold dictionary codes.
                if m not in ("min", "max"):
                    raise HostFallback(
                        f"aggregate '{m}' over string column(s) "
                        f"{sorted(refs & live)} folds dictionary codes; "
                        "host tier aggregates it")
                if len(set(ops)) != 1:
                    raise HostFallback(
                        "mixed-op aggregation with a string column has "
                        "no device combiner; host tier aggregates it")
                dict_specs.add(bn)
        out_pairs = [("k", Col(plan.key))] + [(bn, e)
                                              for bn, e, _m in specs]
        staged = _flush(st, out_pairs, fused)
        _key_dtype(staged, ("int32",))
        exchange = _pick_exchange(ctx, options, st, len(specs) + 1, notes)
        if len(set(ops)) == 1:
            red = staged.reduce_by_key(op=ops[0], exchange=exchange)
            notes.append(f"groupBy: named-op '{ops[0]}' segment reduce")
        else:
            red = staged.reduce_by_key(func=_traced_tuple_combiner(ops),
                                       exchange=exchange)
            notes.append(
                f"groupBy: traced tuple combiner over {ops}")
        out = _DState(red, [(plan.key, "k")] + [
            (bn, bn) for bn, _e, _m in specs],
            dict_cols=(({plan.key} if plan.key in live else set())
                       | dict_specs),
            date_cols=(({plan.key} if plan.key in dlive else set())
                       | date_specs))
        out.est_rows = st.est_rows
        # Mean finalization (and companion drop) rides the NEXT stage.
        proj = [(plan.key, Col(plan.key))]
        for a, slot in zip(plan.aggs, slots):
            if slot[0] == "mean":
                proj.append((a.alias, Col(specs[slot[1]][0])
                             / Col(specs[slot[2]][0])))
            else:
                proj.append((a.alias, Col(specs[slot[1]][0])))
        if any(s[0] == "mean" for s in slots) or any(
                a.alias != specs[s[1]][0]
                for a, s in zip(plan.aggs, slots)):
            out.steps.append(("project", proj))
            if not fused:
                out = _unfused_break(out, plan.columns(), options)
        return out
    if isinstance(plan, L.Join):
        lst = _lower_device(ctx, plan.left, options, notes)
        rst = _lower_device(ctx, plan.right, options, notes)
        lvals = [c for c in plan.left.columns() if c != plan.on]
        rvals = [c for c in plan.right.columns() if c != plan.on]
        ldict = _dicts_after(lst, plan.left.columns())
        rdict = _dicts_after(rst, plan.right.columns())
        ldate = _dates_after(lst, plan.left.columns())
        rdate = _dates_after(rst, plan.right.columns())
        if (plan.on in ldate) != (plan.on in rdate):
            raise HostFallback(
                f"join key {plan.on!r} is a date on one side only; host "
                "tier joins it")
        if plan.how != "inner" and (plan.fill_value is None
                                    or set(rvals) & (rdict | rdate)):
            # The kernel fills an unmatched row's right columns with one
            # scalar: no None, and no string or date a code or a day
            # would decode to.
            raise HostFallback(
                "left join whose fill has no device form (None, or a "
                "string or date right column); host tier joins it")
        # Value columns by position (l0.., r0..): the join keeps each
        # side's names, and positional names can neither collide nor
        # take the canonical VALUE's lv/rv renames.
        lnames = [f"l{i}" for i in range(len(lvals))]
        rnames = [f"r{i}" for i in range(len(rvals))]
        lnode = _flush(lst, [("k", Col(plan.on))]
                       + [(bn, Col(c)) for bn, c in zip(lnames, lvals)],
                       bool(options["fuse"]))
        rnode = _flush(rst, [("k", Col(plan.on))]
                       + [(bn, Col(c)) for bn, c in zip(rnames, rvals)],
                       bool(options["fuse"]))
        _key_dtype(lnode, ("int32",))
        _key_dtype(rnode, ("int32",))
        exchange = _pick_exchange(ctx, options, lst,
                                  1 + len(lvals) + len(rvals), notes)
        from vega_tpu.tpu.dense_rdd import dense_join

        joined = dense_join(lnode, rnode, outer=plan.how != "inner",
                            fill_value=plan.fill_value, exchange=exchange)
        if joined is None:
            raise HostFallback(
                f"join key {plan.on!r} has no common device form on the "
                "two sides (strings against numbers, or key dtypes differ)")
        notes.append(f"join: device sort-merge ({plan.how}), "
                     f"{len(lvals)} x {len(rvals)} value columns")
        out = _DState(joined, [(plan.on, "k")] + list(zip(lvals, lnames))
                      + list(zip(rvals, rnames)),
                      dict_cols=(({plan.on} | set(lvals)) & ldict)
                      | (set(rvals) & rdict),
                      date_cols=(({plan.on} | set(lvals)) & ldate)
                      | (set(rvals) & rdate))
        out.est_rows = lst.est_rows
        return out
    if isinstance(plan, L.Sort):
        st = _lower_device(ctx, plan.child, options, notes)
        others = [c for c in plan.columns() if c != plan.by]
        taken = {"k"}
        pairs = [("k", Col(plan.by))] + [
            (_sanitize(c, taken), Col(c)) for c in others]
        node = _flush(st, pairs, bool(options["fuse"]))
        _key_dtype(node, ("int32", "float32"))
        exchange = _pick_exchange(ctx, options, st, len(pairs), notes)
        sorted_node = node.sort_by_key(ascending=plan.ascending,
                                       exchange=exchange)
        notes.append("sort: device sample-sort exchange")
        out = _DState(sorted_node, [(plan.by, "k")] + list(
            zip(others, [bn for bn, _e in pairs[1:]])),
            dict_cols=_dicts_after(st, plan.columns()),
            date_cols=_dates_after(st, plan.columns()))
        out.est_rows = st.est_rows
        return out
    raise HostFallback(f"no device lowering for {type(plan).__name__}")


def _unfused_break(st: _DState, cols: List[str], options: dict) -> _DState:
    """fuse=False: compile the pending step(s) as their own one-node
    program (chain-broken), so every verb pays its own launch — the
    fusion A/B's control leg."""
    taken: set = set()
    pairs = [(_sanitize(c, taken), Col(c)) for c in cols]
    node = _flush(st, pairs, fused=False)
    out = _DState(node, list(zip(cols, [bn for bn, _e in pairs])),
                  dict_cols=_dicts_after(st, cols),
                  date_cols=_dates_after(st, cols))
    out.est_rows = st.est_rows
    return out


def _traced_tuple_combiner(ops: List[str]):
    """Elementwise monoid combine over the value-column tuple, built from
    jnp primitives so the device reduce traces it — the mixed-op agg path
    (e.g. sum(x), min(y) in one exchange)."""
    import jax.numpy as jnp

    fns = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    picked = [fns[op] for op in ops]
    if len(picked) == 1:
        f0 = picked[0]
        return lambda a, b: f0(a, b)

    def combine(a, b):
        return tuple(f(x, y) for f, x, y in zip(picked, a, b))

    return combine


def _settled(st: _DState, cols: List[str], options: dict):
    """(node, [(frame name, block name)], dictionary columns, date columns)
    of `cols` once the pending steps are flushed."""
    if st.steps:
        taken: set = set()
        pairs = [(_sanitize(c, taken), Col(c)) for c in cols]
        node = _flush(st, pairs, bool(options["fuse"]))
        out = list(zip(cols, [bn for bn, _e in pairs]))
    else:
        node = st.node
        cm = dict(st.colmap)
        out = [(c, cm[c]) for c in cols]
    return node, out, _dicts_after(st, cols), _dates_after(st, cols)


def _compile_device(ctx, plan: L.LogicalPlan, options: dict,
                    limit: Optional[int], notes: List[str]) -> Compiled:
    st = _lower_device(ctx, plan, options, notes)
    cols = plan.columns()
    node, out, _dicts, dates = _settled(st, cols, options)
    return Compiled("device", node, cols, out, "block", limit, plan, notes,
                    dates=dates)


class FrameCache:
    """What a cached frame (DataFrame.cache(), logical.CachedScan) lowers
    to: its plan's lineage, built at the first compile on each tier and
    handed to every later one, so the device node — and the block it
    materializes once, under the HBM LRU like any DenseRDD's — is the same
    object from action to action, and a later action reads no file. A
    plan with no device lowering records why, and every device compile
    falls back for that reason."""

    def __init__(self, plan: L.LogicalPlan):
        self.plan = plan
        self._device = None  # (node, colmap, dicts, dates, est) | fallback
        self._host = None    # (rdd, layout, cols)

    def _optimized(self, options: dict) -> L.LogicalPlan:
        pushdown = bool(options["pushdown"])
        return L.optimize(self.plan, pushdown=pushdown) if pushdown \
            else self.plan

    def device(self, ctx, options: dict, notes: List[str]) -> _DState:
        if self._device is None:
            try:
                st = _lower_device(ctx, self._optimized(options), options,
                                   notes)
                node, out, dicts, dates = _settled(st, self.plan.columns(),
                                                   options)
                node.cache()
                self._device = (node, out, dicts, dates, st.est_rows)
            except HostFallback as e:
                self._device = e
        if isinstance(self._device, HostFallback):
            raise HostFallback(f"cached frame: {self._device}")
        node, out, dicts, dates, est = self._device
        notes.append("cached frame: its resident lineage")
        st = _DState(node, out, dict_cols=dicts, date_cols=dates)
        st.est_rows = est
        return st

    def host(self, ctx, options: dict) -> "_HState":
        if self._host is None:
            st = _host_flush_blocks(
                _lower_host(ctx, self._optimized(options), options))
            st.rdd.cache()
            self._host = (st.rdd, st.layout, st.cols)
        return _host_state(*self._host)


# ---------------------------------------------------------------------------
# host lowering
# ---------------------------------------------------------------------------


class _HState:
    def __init__(self, rdd, layout: str, cols: List[str]):
        self.rdd = rdd
        self.layout = layout  # "blocks" | "rows"
        self.cols = list(cols)
        self.steps: List[tuple] = []  # pending, blocks layout only


def _host_flush_blocks(st: _HState) -> _HState:
    if not st.steps:
        return st
    emit = [(c, Col(c)) for c in st.cols]
    fn = P.host_block_stage([(c, c) for c in st.input_cols], st.steps, emit)
    out = _HState(st.rdd.map(fn), "blocks", st.cols)
    out.input_cols = st.cols
    return out


def _host_state(rdd, layout, cols) -> _HState:
    st = _HState(rdd, layout, cols)
    st.input_cols = list(cols)
    return st


def _host_to_rows(st: _HState) -> _HState:
    st = _host_flush_blocks(st)
    if st.layout == "rows":
        return st
    return _host_state(st.rdd.flat_map(P.host_block_rows(st.cols)),
                       "rows", st.cols)


def _lower_host(ctx, plan: L.LogicalPlan, options: dict) -> _HState:
    if isinstance(plan, L.ColumnsScan):
        data = {nm: np.asarray(c) for nm, c in plan.data.items()}
        cols = list(data)
        n = len(data[cols[0]]) if cols else 0
        parts = plan.num_partitions or ctx.default_parallelism
        per = -(-n // parts) if n else 1
        chunks = [{nm: c[i * per:(i + 1) * per] for nm, c in data.items()}
                  for i in range(max(1, -(-n // per) if n else 1))]
        return _host_state(ctx.parallelize(chunks, len(chunks)),
                           "blocks", cols)
    if isinstance(plan, L.CachedScan):
        return plan.cache.host(ctx, options)
    if isinstance(plan, L.ParquetScan):
        from vega_tpu.io.readers import ParquetColumnReader

        cols = plan.columns()
        reader = ParquetColumnReader(
            plan.path,
            columns=None if plan.columns_kept is None else cols,
            predicate=plan.predicate,
            num_partitions=plan.num_partitions or ctx.default_parallelism)
        return _host_state(ctx.read_source(reader), "blocks", cols)
    if isinstance(plan, L.Project):
        st = _lower_host(ctx, plan.child, options)
        if st.layout == "blocks":
            st.steps.append(("project", list(plan.outputs)))
            st.cols = plan.columns()
            return st
        fn = P.host_rows_stage(st.cols, [],
                               [(nm, e) for nm, e in plan.outputs])
        return _host_state(st.rdd.map(fn), "rows", plan.columns())
    if isinstance(plan, L.Filter):
        st = _lower_host(ctx, plan.child, options)
        if st.layout == "blocks":
            st.steps.append(("filter", plan.predicate))
            return st
        return _host_state(
            st.rdd.filter(P.host_rows_filter(st.cols, plan.predicate)),
            "rows", st.cols)
    if isinstance(plan, L.GroupAgg):
        import operator

        st = _lower_host(ctx, plan.child, options)
        specs, slots = _agg_specs(plan)
        spec_pairs = [(bn, e) for bn, e, _m in specs]
        ops = [m for _bn, _e, m in specs]
        # Single-aggregate plans shuffle BARE scalars with the canonical
        # monoid callable: _infer_named_op tags the Aggregator, the C++
        # bucket combine kicks in, and — the planner picking shuffle
        # policy per exchange — the push plan (shuffle_plan=push) can
        # pre-merge it server-side, which tuple-valued combines cannot.
        scalar = len(specs) == 1 and ops[0] in ("add", "min", "max")
        if st.layout == "blocks":
            st = _host_flush_blocks(st)
            pairs = st.rdd.flat_map(
                P.host_block_to_pairs(plan.key, spec_pairs, scalar=scalar))
        else:
            pairs = st.rdd.map(
                P.host_rows_to_pairs(st.cols, plan.key, spec_pairs,
                                     scalar=scalar))
        if scalar:
            monoid = {"add": operator.add, "min": min, "max": max}[ops[0]]
            rows = pairs.reduce_by_key(monoid).map(P.host_pair_to_row())
        else:
            reduced = pairs.reduce_by_key(P.host_tuple_combiner(ops))
            rows = reduced.map(P.host_finalize_slots(slots))
        return _host_state(rows, "rows", plan.columns())
    if isinstance(plan, L.Join):
        lst = _host_to_rows(_lower_host(ctx, plan.left, options))
        rst = _host_to_rows(_lower_host(ctx, plan.right, options))
        li = lst.cols.index(plan.on)
        ri = rst.cols.index(plan.on)
        lp = lst.rdd.map(P.host_row_to_pair(li))
        rp = rst.rdd.map(P.host_row_to_pair(ri))
        if plan.how == "inner":
            rows = lp.join(rp).map(P.host_join_rows())
        else:
            r_arity = len(rst.cols) - 1
            rows = lp.cogroup(rp).flat_map(
                P.host_left_join_emit(r_arity, plan.fill_value))
        return _host_state(rows, "rows", plan.columns())
    if isinstance(plan, L.Sort):
        st = _host_to_rows(_lower_host(ctx, plan.child, options))
        idx = st.cols.index(plan.by)
        rows = st.rdd.sort_by(_row_key(idx), ascending=plan.ascending)
        return _host_state(rows, "rows", st.cols)
    raise VegaError(f"no host lowering for {type(plan).__name__}")


def _row_key(idx: int):
    def key(row):
        return row[idx]

    return key


def _compile_host(ctx, plan: L.LogicalPlan, options: dict,
                  limit: Optional[int], notes: List[str]) -> Compiled:
    st = _lower_host(ctx, plan, options)
    st = _host_flush_blocks(st)
    cols = plan.columns()
    return Compiled("host", st.rdd, cols, [(c, c) for c in cols],
                    st.layout, limit, plan, notes)

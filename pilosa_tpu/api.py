"""API façade: every externally triggerable action, validated against the
cluster state machine.

Parity target: the reference's ``*pilosa.API`` (api.go:42).  Each public
method checks the cluster state against a per-method validation table
(api.go:119 ``validate`` / api.go:1343 ``methodsNormal`` etc.) before
touching the holder/executor, so callers — the HTTP handler, the CLI,
tests — share one enforcement point.
"""

from __future__ import annotations

import base64 as _b64mod
import io

import numpy as np


def _ts_iso(ts):
    return None if ts is None else ts.isoformat()

from pilosa_tpu import observe as _observe
from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.index import IndexOptions
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.version import VERSION


class ApiError(Exception):
    """Base API error; http layer maps subclasses to status codes."""


class NotFoundError(ApiError):
    pass


class ConflictError(ApiError):
    pass


class ApiMethodNotAllowedError(ApiError):
    """Method not valid for the current cluster state (api.go:114)."""


# Per-method allowed cluster states (reference api.go:1343-end).  Methods
# absent from this table are allowed in any state.
_NORMAL = frozenset({"NORMAL"})
_QUERY = frozenset({"NORMAL", "DEGRADED"})
_RESIZE_OK = frozenset({"NORMAL", "STARTING", "RESIZING", "DEGRADED"})

_METHOD_STATES = {
    "query": _QUERY,
    "create_index": _NORMAL,
    "delete_index": _NORMAL,
    "create_field": _NORMAL,
    "delete_field": _NORMAL,
    "delete_view": _NORMAL,
    "import_bits": _NORMAL,
    "import_values": _NORMAL,
    "import_roaring": _NORMAL,
    "export_csv": _NORMAL,
    "apply_schema": _NORMAL,
    "set_coordinator": _RESIZE_OK,
    "remove_node": _NORMAL,
    "resize_abort": frozenset({"RESIZING"}),
    "recalculate_caches": _QUERY,
}


class API:
    """Façade over one node's holder + cluster + executor (api.go:42)."""

    def __init__(self, node):
        """`node` is a pilosa_tpu.parallel.node.ClusterNode."""
        self.node = node
        self.holder = node.holder
        self.cluster = node.cluster
        self.executor = node.executor
        self.max_writes_per_request = 0  # 0 = unlimited (config wired by server)

    # ----------------------------------------------------------- validate

    def _validate(self, method: str) -> None:
        allowed = _METHOD_STATES.get(method)
        if allowed is None:
            return
        state = self.cluster.state
        if state not in allowed:
            raise ApiMethodNotAllowedError(
                f"api method {method} not allowed in cluster state {state}"
            )

    # -------------------------------------------------------------- query

    def query(self, index: str, pql, shards=None, remote: bool = False,
              column_attrs: bool = False, exclude_row_attrs: bool = False,
              exclude_columns: bool = False, coalesce: bool = True,
              cache: bool = True, delta: bool = True,
              containers: bool = True, mesh: bool = True,
              tiers: bool = True, vm: bool = True,
              partial: bool = False,
              partial_meta: dict | None = None,
              tenant: str | None = None):
        """Execute PQL -> list of results (api.go:135 API.Query).

        ``partial=True`` (the HTTP layer's ?partial=1 /
        X-Pilosa-Partial) degrades instead of erroring when shards
        exhaust every replica: results come back with the reachable
        shards only, and ``partial_meta`` (when given) is filled with
        ``missingShards`` (the exact unavailable set) and
        ``missingFraction``.  The default keeps all-or-error
        semantics on an identical code path.

        ``tenant`` is the request's tenant id (the HTTP layer's
        X-Pilosa-Tenant / ?tenant=): it rides ExecOptions into the
        executor, where admission quotas, result-cache soft budgets
        and residency tier quotas charge it ([tenants] isolation;
        inert while disabled)."""
        from pilosa_tpu.parallel.executor import ExecOptions
        from pilosa_tpu.serve import deadline as _deadline

        self._validate("query")
        # end-to-end deadline: the handler installed the request's
        # X-Pilosa-Deadline scope on this thread; expired budgets shed
        # here, before translate/collective work touches anything
        dl = _deadline.current()
        _deadline.check(dl, "query execution")
        if (not remote and shards is None and not partial
                and isinstance(pql, str)):
            # multi-process runtime: the coordinator upgrades supported
            # reads to one collective SPMD program over the global mesh
            # (parallel/spmd.py); None falls through to scatter-gather.
            # This check runs BEFORE the write-limit branch below, which
            # rebinds pql to a parsed Query and would otherwise make the
            # upgrade unreachable on config-launched servers.
            from pilosa_tpu import tracing as _tracing
            from pilosa_tpu.parallel import spmd

            # the collective upgrade bypasses the executor, so its
            # flight record is opened (and, when the upgrade declines,
            # discarded) here — but only when a collective runtime
            # exists at all: on the default single-node path the
            # executor opens the one record, and a begin/discard pair
            # here would double the recorder cost per query
            recorder = getattr(self.executor, "recorder", None)
            rec = None
            if (recorder is not None and recorder.enabled
                    and spmd.collective_available()):
                rec = recorder.begin(index, pql,
                                     trace_id=_tracing.active_trace_id())
                rec.tenant = tenant
            try:
                # the collective upgrade bypasses the executor, so the
                # tenant scope the executor would install goes here —
                # without it, cache fills and residency admissions on
                # this path charge the default tier, escaping the
                # requesting tenant's quotas
                from pilosa_tpu.serve import tenant as _tenantmod

                with _observe.attach(rec), _tenantmod.scope(tenant):
                    res = spmd.try_collective(
                        self.node, index, pql,
                        exclude_row_attrs=exclude_row_attrs)
            except BaseException as e:
                if rec is not None:
                    recorder.publish(rec,
                                     error=f"{type(e).__name__}: {e}")
                raise
            if res is not None:
                if rec is not None:
                    rec.note_path("collective")
                    rec.note_engine("collective")
                    rec.result_sizes = [_observe.result_size(r)
                                        for r in res]
                    recorder.publish(rec)
                return res
            if rec is not None:
                recorder.discard(rec)
        # ``api.open``: from where the handler's last span ended (the
        # body read) to the executor's call: the route's dispatch, this
        # method's prologue, the parse (its child) and the options
        text = pql if isinstance(pql, str) else None
        with _observe.span("api.open", start_ns=_observe.since()):
            if self.max_writes_per_request > 0:
                from pilosa_tpu.pql import Query, parse as _parse

                # the parsed Query skips the executor's re-parse, so
                # the sentinel gate must apply here too (remote-only
                # spellings)
                q = pql
                if isinstance(pql, str):
                    with _observe.span("pql.parse"):
                        q = _parse(pql, allow_internal=remote)
                if isinstance(q, Query) and (
                        q.write_call_n() > self.max_writes_per_request):
                    raise ApiError(
                        f"too many writes in one request "
                        f"({q.write_call_n()} > "
                        f"{self.max_writes_per_request})")
                pql = q
            opt = ExecOptions(
                remote=remote,
                column_attrs=column_attrs,
                exclude_row_attrs=exclude_row_attrs,
                exclude_columns=exclude_columns,
                shards=None if shards is None else list(shards),
                coalesce=coalesce,
                cache=cache,
                delta=delta,
                containers=containers,
                mesh=mesh,
                tiers=tiers,
                vm=vm,
                deadline=dl,
                partial=partial,
                missing=set() if partial else None,
                tenant=tenant,
            )
        results = self.executor.execute(index, pql, opt=opt, text=text)
        if partial_meta is not None:
            miss = sorted(opt.missing or ())
            partial_meta["missingShards"] = miss
            partial_meta["missingFraction"] = (
                round(len(miss) / opt.targeted, 4) if opt.targeted
                else 0.0)
        return results

    # ------------------------------------------------------------- schema

    def schema(self) -> list[dict]:
        return self.holder.schema()

    def apply_schema(self, schema: list[dict]) -> None:
        """Idempotent schema merge (api.go ApplySchema)."""
        self._validate("apply_schema")
        self.holder.apply_schema(schema)

    def index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise NotFoundError(f"index not found: {name}")
        return idx

    def create_index(self, name: str, options: IndexOptions | None = None):
        self._validate("create_index")
        if self.holder.index(name) is not None:
            raise ConflictError(f"index already exists: {name}")
        return self.node.create_index(name, options)

    def delete_index(self, name: str) -> None:
        self._validate("delete_index")
        if self.holder.index(name) is None:
            raise NotFoundError(f"index not found: {name}")
        self.node.delete_index(name)

    def field(self, index: str, name: str):
        idx = self.index(index)
        f = idx.field(name)
        if f is None:
            raise NotFoundError(f"field not found: {name}")
        return f

    def create_field(self, index: str, name: str,
                     options: FieldOptions | None = None):
        self._validate("create_field")
        idx = self.index(index)
        if idx.field(name) is not None:
            raise ConflictError(f"field already exists: {name}")
        return self.node.create_field(index, name, options)

    def delete_field(self, index: str, name: str) -> None:
        self._validate("delete_field")
        self.field(index, name)
        self.node.delete_field(index, name)

    # ------------------------------------------------------------- import

    def import_bits(self, index: str, field: str, rows, cols,
                    timestamps=None, row_keys=None, col_keys=None,
                    clear: bool = False, remote: bool = False) -> None:
        """Bulk bit import: translate keys, group bits by shard, and
        forward each group to every owner replica — local owners import
        directly (api.go:920 API.Import; client-side shard routing
        http/client.go:1164 GroupByShard + per-owner POST)."""
        self._validate("import_bits")
        idx = self.index(index)
        f = self.field(index, field)
        if col_keys:
            cols = self._translate_keys(index, None, col_keys)
        if row_keys:
            cols_n = len(cols)
            rows = self._translate_keys(index, field, row_keys)
            if len(rows) != cols_n:
                raise ApiError("row keys and columns length mismatch")
        # ndarrays (the protobuf bulk path) pass through untouched —
        # field.import_bits groups them vectorized; anything else
        # becomes a list once here
        if not isinstance(rows, np.ndarray):
            rows = list(rows)
        if not isinstance(cols, np.ndarray):
            cols = list(cols)
        if remote or not self._clustered():
            f.import_bits(rows, cols, timestamps, clear=clear)
            if not clear:
                idx.import_existence(cols)
            return
        known_shards = f.available_shards()
        for shard, sel in self._group_by_shard(cols).items():
            # the bus payload is JSON — ndarray selections convert via
            # fancy-index + tolist (C speed), list inputs via comp
            def pick(seq, to_list: bool, sel=sel):
                # sel bound at definition: local_fn runs inside
                # _send_to_owners, but never risk the loop variable
                if isinstance(seq, np.ndarray):
                    out = seq[sel]
                    return out.tolist() if to_list else out
                return [seq[i] for i in sel]

            payload = {
                "type": "import",
                "index": index,
                "field": field,
                "rows": pick(rows, True),
                "cols": pick(cols, True),
                "timestamps": None if timestamps is None else
                    [_ts_iso(timestamps[i]) for i in sel],
                "clear": clear,
            }
            self._send_to_owners(
                index, shard, payload,
                # pick=pick: pick rebinds every iteration, so the
                # lambda must be self-contained even if delivery is
                # ever deferred past this loop step
                local_fn=lambda sel=sel, pick=pick: (
                    f.import_bits(
                        pick(rows, False), pick(cols, False),
                        None if timestamps is None
                        else [timestamps[i] for i in sel],
                        clear=clear,
                    ),
                    None if clear else idx.import_existence(
                        pick(cols, False)),
                ),
            )
            self._note_shard_everywhere(f, index, field, shard,
                                        known=shard in known_shards)

    def import_values(self, index: str, field: str, cols, values,
                      col_keys=None, remote: bool = False) -> None:
        """Bulk BSI import with shard routing (api.go:1000
        API.ImportValue)."""
        self._validate("import_values")
        idx = self.index(index)
        f = self.field(index, field)
        if col_keys:
            cols = self._translate_keys(index, None, col_keys)
        cols, values = list(cols), list(values)
        if remote or not self._clustered():
            f.import_values(cols, values)
            idx.import_existence(cols)
            return
        known_shards = f.available_shards()
        for shard, sel in self._group_by_shard(cols).items():
            payload = {
                "type": "import-value",
                "index": index,
                "field": field,
                "cols": [cols[i] for i in sel],
                "values": [values[i] for i in sel],
            }
            self._send_to_owners(
                index, shard, payload,
                local_fn=lambda sel=sel: (
                    f.import_values([cols[i] for i in sel],
                                    [values[i] for i in sel]),
                    idx.import_existence([cols[i] for i in sel]),
                ),
            )
            self._note_shard_everywhere(f, index, field, shard,
                                        known=shard in known_shards)

    def _translate_keys(self, index: str, field: str | None, keys):
        """Key creation with single-writer routing (api.go:920 import
        key translation; holder.go:690 primary-only writes).  All
        routing lives in node.translate_keys_cluster."""
        return self.node.translate_keys_cluster(index, field, keys,
                                                create=True)

    def _clustered(self) -> bool:
        return (self.cluster.transport is not None
                and len(self.cluster.sorted_nodes()) > 1)

    @staticmethod
    def _group_by_shard(cols) -> dict:
        """shard -> selection of indices into ``cols`` (list of ints
        for list input, ndarray for ndarray input — both index back
        into the parallel rows/cols sequences)."""
        if isinstance(cols, np.ndarray):
            from pilosa_tpu.ops.bitmap import group_indices

            return group_indices(cols // SHARD_WIDTH)
        by_shard: dict[int, list[int]] = {}
        for i, c in enumerate(cols):
            by_shard.setdefault(c // SHARD_WIDTH, []).append(i)
        return by_shard

    def _note_shard_everywhere(self, f, index: str, field: str,
                               shard: int, known: bool) -> None:
        """Record shard existence locally and broadcast it so every
        node's available-shard bitmap includes it (reference
        CreateShardMessage, view.go:263-305)."""
        f._note_shard(shard)
        if not known:
            self.node.note_shard_created(index, field, shard)

    def _send_to_owners(self, index: str, shard: int, payload: dict,
                        local_fn) -> None:
        """Deliver one shard's import to all owner replicas;
        unreachable peers are skipped (anti-entropy reconciles, like
        the reference's best-effort replication).

        A peer REFUSING as non-owner (reference api.go
        ErrClusterDoesNotOwnShard) means its membership view is
        fresher than ours — a resize just re-homed the shard.  The
        fan-out then waits for the status broadcast to land,
        re-resolves the owner set, and retries the refused deliveries;
        if the views never converge it raises instead of silently
        dropping a write on an ex-owner (whose fragments the
        post-resize sweep deletes)."""
        from pilosa_tpu.parallel.cluster import converge_owner_deliveries
        from pilosa_tpu.serve.admission import rpc_class

        applied: set[str] = set()

        def on_timeout() -> None:
            raise ApiError(
                f"shard {shard} owners refused the import as "
                "non-owners and the membership view did not "
                "converge; retry")

        # replica deliveries carry the ingest class on the wire so the
        # receiving node admits them against its ingest gate, not the
        # internal one anti-entropy competes in
        with rpc_class("ingest"):
            converge_owner_deliveries(
                lambda: self._owner_pass(index, shard, payload, local_fn,
                                         applied),
                on_timeout)

    def _owner_pass(self, index: str, shard: int, payload: dict,
                    local_fn, applied: set) -> bool:
        """One delivery sweep over the CURRENT owner set, skipping
        nodes already applied.  Returns True if any owner refused as
        non-owner (caller retries after the view converges)."""
        from pilosa_tpu.parallel.cluster import TransportError

        refused = False
        # write_nodes = serving owners + PENDING owners mid-rebalance:
        # imports dual-write during a migration so the new owner's
        # copy converges bit-exact without waiting for anti-entropy
        for n in self.cluster.write_nodes(index, shard):
            if n.id in applied:
                continue
            if n.id == self.cluster.local_id:
                local_fn()
                applied.add(n.id)
                continue
            try:
                resp = self.cluster.transport.send_message(n, payload)
            except TransportError:
                applied.add(n.id)  # unreachable: AE reconciles later
                continue
            if isinstance(resp, dict) and resp.get("unowned"):
                refused = True
                continue
            applied.add(n.id)
        return refused

    def import_roaring(self, index: str, field: str, shard: int,
                       views: dict[str, bytes], clear: bool = False,
                       remote: bool = False) -> None:
        """Merge serialized roaring bitmaps per view into one shard's
        fragments, replicated to every shard owner (api.go:368
        API.ImportRoaring: the origin forwards to all owners with
        remote=true; remote receivers apply locally only)."""
        self._validate("import_roaring")
        from pilosa_tpu.models.field import FieldType
        from pilosa_tpu.models.view import VIEW_STANDARD

        f = self.field(index, field)
        if f.options.type not in (FieldType.SET, FieldType.TIME):
            raise ApiError("roaring import is only supported for set "
                           "and time fields")

        def apply_local() -> None:
            for vname, data in views.items():
                view = f.create_view_if_not_exists(vname or VIEW_STANDARD)
                frag = view.create_fragment_if_not_exists(shard)
                frag.import_roaring(data, clear=clear)
                f._note_shard(shard)

        if remote or not self._clustered():
            apply_local()
            return
        known_shards = f.available_shards()
        payload = {
            "type": "import-roaring",
            "index": index,
            "field": field,
            "shard": shard,
            "views": {vname: _b64mod.b64encode(data).decode()
                      for vname, data in views.items()},
            "clear": clear,
        }
        self._send_to_owners(index, shard, payload, local_fn=apply_local)
        self._note_shard_everywhere(f, index, field, shard,
                                    known=shard in known_shards)

    def export_csv(self, index: str, field: str, shard: int, w: io.TextIOBase) -> None:
        """Write `row,col` (or translated keys) CSV for one shard
        (api.go:500 API.ExportCSV)."""
        self._validate("export_csv")
        from pilosa_tpu.models.view import VIEW_STANDARD

        idx = self.index(index)
        f = self.field(index, field)
        view = f.view(VIEW_STANDARD)
        if view is None:
            return
        frag = view.fragment(shard)
        if frag is None:
            return
        base = shard * SHARD_WIDTH
        for row_id in frag.row_ids():
            words = frag.row(row_id)
            offs = _word_bits(words)
            row_label = row_id
            if f.options.keys:
                row_label = f.translate_store.translate_id(row_id) or row_id
            for off in offs:
                col = base + int(off)
                col_label = col
                if idx.options.keys:
                    col_label = idx.translate_store.translate_id(col) or col
                w.write(f"{row_label},{col_label}\n")

    # ------------------------------------------------------------ cluster

    def hosts(self) -> list[dict]:
        return [n.to_dict() for n in self.cluster.sorted_nodes()]

    def recalculate_caches(self, remote: bool = False) -> None:
        """Force every node's TopN caches up to date (reference
        API.RecalculateCaches, api.go:1139: local recalc + broadcast;
        used by clients that need fresh ranks immediately)."""
        self._validate("recalculate_caches")
        self.node.recalculate_caches()
        if not remote:
            self.node.broadcast({"type": "recalculate-caches"})

    def node_info(self) -> dict:
        return self.cluster.local_node.to_dict()

    def state(self) -> str:
        return self.cluster.state

    def info(self) -> dict:
        return {
            "shardWidth": SHARD_WIDTH,
            "memory": None,
            "cpuType": "tpu+host",
            "cpuPhysicalCores": None,
            "cpuLogicalCores": None,
        }

    def version(self) -> str:
        return VERSION

    def shards_max(self) -> dict[str, int]:
        """index -> max shard (handler /internal/shards/max)."""
        out = {}
        for d in self.holder.schema():
            idx = self.holder.index(d["name"])
            shards = idx.available_shards()
            if shards:
                out[d["name"]] = max(shards)
        return out

    def shard_nodes(self, index: str, shard: int) -> list[dict]:
        return [n.to_dict() for n in self.cluster.shard_nodes(index, shard)]

    def set_coordinator(self, node_id: str) -> None:
        self._validate("set_coordinator")
        if self.cluster.node(node_id) is None:
            raise NotFoundError(f"node not found: {node_id}")
        self.node.set_coordinator(node_id)

    def remove_node(self, node_id: str) -> dict:
        self._validate("remove_node")
        n = self.cluster.node(node_id)
        if n is None:
            raise NotFoundError(f"node not found: {node_id}")
        removed = n.to_dict()
        self.node.remove_node(node_id)
        return removed

    def resize_abort(self) -> None:
        driver = getattr(self.node, "rebalance", None)
        if driver is not None and driver.active():
            # an ONLINE rebalance runs with the cluster state NORMAL
            # (that is the whole point), so the legacy RESIZING-only
            # state gate must not block its abort
            self.node.resize_abort()
            return
        self._validate("resize_abort")
        self.node.resize_abort()

    def cluster_resize(self, body: dict) -> dict:
        """POST /cluster/resize: node add/remove as a control-plane
        operation.  ``mode: "online"`` (the default) drives the live
        per-shard migration (parallel/rebalance.py) — the cluster
        keeps serving throughout; ``mode: "offline"`` is the legacy
        stop-the-world resize (byte-identical behavior: the whole
        cluster goes RESIZING and refuses queries for the duration),
        kept as an explicit escape hatch.

        Body: ``{"mode": "online"|"offline", "add": {node dict}}`` or
        ``{"mode": ..., "removeId": "node-id"}`` (exactly one of
        add/removeId); online accepts ``"background": false`` for
        synchronous runs (tests)."""
        mode = (body.get("mode") or "online").lower()
        if mode not in ("online", "offline"):
            raise ApiError(
                f"unknown resize mode {mode!r} (online|offline)")
        add = body.get("add")
        remove_id = body.get("removeId") or body.get("remove_id")
        if (add is None) == (remove_id is None):
            raise ApiError(
                "exactly one of 'add' or 'removeId' is required")
        if mode == "offline":
            if add is not None:
                resp = self.node.receive_message(
                    {"type": "node-join", "node": add})
                return {"mode": "offline", "applied": True,
                        "response": resp}
            self._validate("remove_node")
            if self.cluster.node(remove_id) is None:
                raise NotFoundError(f"node not found: {remove_id}")
            self.node.remove_node(remove_id)
            return {"mode": "offline", "applied": True}
        driver = getattr(self.node, "rebalance", None)
        if driver is None:
            raise ApiError(
                "no rebalance driver attached to this node; use "
                'mode "offline" or target a server-assembled node')
        from pilosa_tpu.parallel.cluster import Node as _Node
        from pilosa_tpu.parallel.rebalance import RebalanceError

        try:
            out = driver.start(
                add=None if add is None else _Node.from_dict(add),
                remove_id=remove_id,
                background=bool(body.get("background", True)))
        except RebalanceError as e:
            raise ConflictError(str(e))
        out["mode"] = "online"
        return out

    def rebalance_status(self) -> dict:
        """The /debug/rebalance document (driver status + counters);
        a bare node without an attached driver reports inactive."""
        driver = getattr(self.node, "rebalance", None)
        if driver is None:
            from pilosa_tpu.parallel import rebalance as _rebalance

            return {"active": False, "attached": False,
                    "counters": _rebalance.counters()}
        out = driver.status()
        out["attached"] = True
        return out

    # ------------------------------------------------------ anti-entropy

    def fragment_blocks(self, index: str, field: str, view: str, shard: int):
        f = self.field(index, field)
        v = f.view(view)
        if v is None:
            raise NotFoundError(f"view not found: {view}")
        frag = v.fragment(shard)
        if frag is None:
            raise NotFoundError(f"fragment not found: shard {shard}")
        return frag.blocks()

    def fragment_block_data(self, index: str, field: str, view: str,
                            shard: int, block: int):
        f = self.field(index, field)
        v = f.view(view)
        frag = None if v is None else v.fragment(shard)
        if frag is None:
            raise NotFoundError(f"fragment not found: shard {shard}")
        return frag.block_data(block)

    def fragment_data(self, index: str, field: str, view: str, shard: int) -> bytes:
        """Serialized fragment (roaring) for resize transfer
        (api.go FragmentData / fragment.go:2436 WriteTo)."""
        f = self.field(index, field)
        v = f.view(view)
        frag = None if v is None else v.fragment(shard)
        if frag is None:
            raise NotFoundError(f"fragment not found: shard {shard}")
        return frag.to_roaring()

    # ---------------------------------------------------------- translate

    def translate_data(self, index: str, field: str | None, after: int,
                       limit: int = 10000):
        """Tail the primary's translate entry stream
        (api.go TranslateData / http/translator.go:30)."""
        if field:
            store = self.field(index, field).translate_store
        else:
            store = self.index(index).translate_store
        return store.entries(after, limit)


def _word_bits(words: np.ndarray) -> np.ndarray:
    """Bit offsets set in a packed little-endian word array."""
    if words is None or len(words) == 0:
        return np.empty(0, dtype=np.int64)
    bits = np.unpackbits(
        np.asarray(words).view(np.uint8), bitorder="little"
    )
    return np.nonzero(bits)[0]

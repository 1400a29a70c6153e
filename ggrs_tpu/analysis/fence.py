"""Pass 3 — fence-discipline lint (FEN001).

The async dispatch pipeline's correctness argument (PR 1/3/4) is a
*protocol*: pooled staging buffers may be reused only because the fence
proves the dispatch that read them retired; the inflight deque and its
row count ARE the fence; the shared plan cache is the jit-cache bound.
Mutating any of that state from a method outside the fence/dispatch
entry points silently breaks the proof — the buffer gets reused while a
dispatch may still read it, or the backpressure signal drifts from the
real in-flight window.

This pass encodes the protocol as a policy table: per protected module,
the attribute names that make up device-core shared state and the
methods allowed to write them. A write is an attribute assignment
(`x._inflight = ...`, `x.rings = ...`), an augmented assignment, a
subscript store through the attribute, or a mutating container-method
call (`x._inflight.append(...)`). Reads are always fine; so are calls to
the entry points themselves (that's the routed path).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from .engine import Repo, enclosing_class, finding, parent_of, qualname_of
from .findings import Finding

_MUTATORS = {
    "append", "appendleft", "extend", "insert", "remove", "pop", "popleft",
    "clear", "update", "setdefault", "add", "discard", "fill", "sort",
    "reverse",
}

# device-core shared state: the async-fence carry, the pooled staging
# buffers, and the dispatch-plan cache
CORE_STATE: FrozenSet[str] = frozenset({
    "_inflight", "rows_this_tick",
    "_stage_pool", "_stage_pools", "_stage_flip",
    "_multi_bufs", "_multi_flip", "_multi_active", "_multi_count",
    "_pad_row", "_tick_rows", "_tick_future", "_buffered_last_active",
    "plan_cache", "dispatch_signatures",
    "rings", "states",
    "_draft_stage_pools",
    # the device-resident input mailbox (tpu/mailbox.py) and its row
    # ring: the mailbox's INTERNAL staging has its own policy below;
    # from the core's side, the mailbox binding and the ring may only
    # be rebound by the attach/commit/drive/warmup entry points
    "mailbox", "rows_dev",
})

# the mailbox's own shared state: the host-side fill-cycle image (counts,
# staged rows, the open cycle's future checksum batch) and the pooled
# commit staging — reused across commits only under the core's fence
# guarantee, so only the mailbox's own entry points may write them
MAILBOX_STATE: FrozenSet[str] = frozenset({
    "rows_dev", "_counts", "_staged", "pending_rows", "_future",
    "_pools", "_cycle_max_last_active", "_cycle_all_fast", "_vt_fast",
})


@dataclass(frozen=True)
class FencePolicy:
    protected: FrozenSet[str]
    # (class qualname or "*", method name) pairs allowed to write
    allowed: FrozenSet[Tuple[str, str]]


# the fence/dispatch entry points per protected module. serve/host.py
# deliberately has NO allowances: the host must drive the device core
# through its methods (dispatch/begin_tick/reset_slot/...), never by
# reaching into `self.device.<state>`.
POLICIES: Dict[str, FencePolicy] = {
    "ggrs_tpu/tpu/backend.py": FencePolicy(
        protected=CORE_STATE,
        allowed=frozenset({
            ("TpuRollbackBackend", "__init__"),
            ("TpuRollbackBackend", "_note_inflight"),
            ("TpuRollbackBackend", "_next_stage"),
            ("TpuRollbackBackend", "_acquire_multi_buf"),
            ("TpuRollbackBackend", "_run_segment"),
            ("TpuRollbackBackend", "flush"),
            ("TpuRollbackBackend", "reset"),
            ("TpuRollbackBackend", "block_until_ready"),
            ("MultiSessionDeviceCore", "__init__"),
            ("MultiSessionDeviceCore", "_note_inflight"),
            ("MultiSessionDeviceCore", "begin_tick"),
            ("MultiSessionDeviceCore", "_acquire_stage"),
            ("MultiSessionDeviceCore", "dispatch"),
            # dispatch_rows shares the staged tail; the masked batch
            # reset is the env workload's slot lifecycle (auto-reset)
            ("MultiSessionDeviceCore", "_dispatch_staged"),
            ("MultiSessionDeviceCore", "reset_slots_masked"),
            ("MultiSessionDeviceCore", "reset_slot"),
            ("MultiSessionDeviceCore", "warmup"),
            ("MultiSessionDeviceCore", "_warmup_impl"),
            ("MultiSessionDeviceCore", "block_until_ready"),
            ("MultiSessionDeviceCore", "restore"),
            ("MultiSessionDeviceCore", "load_stacked"),
            # live-migration slot adoption: eager per-leaf writes behind
            # a full fence flush, the same discipline as reset_slot
            ("MultiSessionDeviceCore", "import_slot"),
            # speculative bubble-filling: the draft rollout stages rows
            # through its own fenced pool (reads rings only — no
            # stacked-world write), and the per-slot adopt writes the
            # stacked worlds through the same fence discipline as
            # dispatch
            ("MultiSessionDeviceCore", "draft"),
            ("MultiSessionDeviceCore", "adopt_slot"),
            ("MultiSessionDeviceCore", "_acquire_draft_stage"),
            # the device-resident loop's write/harvest entry points: the
            # mailbox attaches once, commits admit the scatter to the
            # fence, and the driver dispatch rebinds the stacked worlds
            # under the same discipline as dispatch
            ("MultiSessionDeviceCore", "attach_mailbox"),
            ("MultiSessionDeviceCore", "commit_mailbox"),
            ("MultiSessionDeviceCore", "drive_mailbox"),
            # device fault domains: the SDC bit-flip injector is the ONE
            # sanctioned direct corruption of the stacked worlds (fault
            # seam / tests only, eager per-slot writes behind a full
            # fence flush — the reset_slot discipline); the quarantine
            # rebuild path reuses reset_slot/import_slot above
            ("MultiSessionDeviceCore", "inject_slot_bitflip"),
            # the session-mesh serving core's fence-dispatch entry
            # points: overrides of the SAME protocol methods (GSPMD row
            # constraints + per-shard instruments wrapped around the
            # inherited fence discipline), listed under their subclass
            # qualnames so a future write routed through them stays
            # inside the policy instead of silently outside it
            ("ShardedMultiSessionDeviceCore", "__init__"),
            ("ShardedMultiSessionDeviceCore", "_dispatch_staged"),
            ("ShardedMultiSessionDeviceCore", "_warmup_impl"),
            # the plan cache's own accounting lives in its own class
            ("DispatchPlanCache", "__init__"),
            ("DispatchPlanCache", "note"),
            ("DispatchPlanCache", "clear"),
        }),
    ),
    "ggrs_tpu/serve/host.py": FencePolicy(
        protected=CORE_STATE,
        allowed=frozenset(),
    ),
    # the multi-process fleet rides the SAME device cores from another
    # process boundary: wire tickets export/import slots and the agent
    # drives the host — all of it must go through the core's entry
    # points above, never by reaching into `host.device.<state>` (zero
    # allowances, the serve/host.py discipline)
    "ggrs_tpu/fleet/ticket.py": FencePolicy(
        protected=CORE_STATE,
        allowed=frozenset(),
    ),
    "ggrs_tpu/fleet/agent.py": FencePolicy(
        protected=CORE_STATE,
        allowed=frozenset(),
    ),
    "ggrs_tpu/fleet/island.py": FencePolicy(
        protected=CORE_STATE,
        allowed=frozenset(),
    ),
    # the device-resident input mailbox: the donated row ring, the
    # host-side fill-cycle image and the pooled commit staging are the
    # resident loop's correctness protocol — a write outside the
    # stage/commit/cycle entry points breaks the fence-reuse proof or
    # desynchronizes the watermarks from the rows the device will read
    "ggrs_tpu/tpu/mailbox.py": FencePolicy(
        protected=MAILBOX_STATE,
        allowed=frozenset({
            ("DeviceMailbox", "__init__"),
            ("DeviceMailbox", "stage"),
            ("DeviceMailbox", "commit"),
            ("DeviceMailbox", "_acquire_commit_stage"),
            ("DeviceMailbox", "take_cycle"),
            ("DeviceMailbox", "warmup"),
            # slot-quarantine containment: scrub one poisoned lane's
            # staged rows + watermark so its committed rows mask to the
            # pad row at the next drive (survivor lanes untouched)
            ("DeviceMailbox", "drop_lane"),
        }),
    ),
    # the durable input journal's writer protocol (journal/wal.py): the
    # active segment fd, the rotation indices and the resume-verify set
    # are the crash-consistency proof — an append or rotation routed
    # around the entry points could tear a record the open-time scan
    # would then misread as the OLD format's torn tail, or leave the
    # verify set claiming rows the disk never saw
    "ggrs_tpu/journal/wal.py": FencePolicy(
        protected=frozenset({
            "_fd", "_seg_index", "_seg_size", "_since_fsync", "_verify",
        }),
        allowed=frozenset({
            ("JournalWriter", "__init__"),
            ("JournalWriter", "_rotate"),
            ("JournalWriter", "_rebase_segment"),
            ("JournalWriter", "append_rows"),
            ("JournalWriter", "verify_row"),
            ("JournalWriter", "sync"),
            ("JournalWriter", "close"),
        }),
    ),
    # the host-side journal tap and the fleet recovery path drive the
    # writer ONLY through its entry points (and the device cores only
    # through theirs) — zero allowances, the serve/host.py discipline
    "ggrs_tpu/journal/recover.py": FencePolicy(
        protected=CORE_STATE | frozenset({
            "_fd", "_seg_index", "_seg_size", "_since_fsync", "_verify",
        }),
        allowed=frozenset(),
    ),
    # the batched wire pump's pooled decode staging (network/pump.py):
    # the offset/length scratch is reused across pump passes — only the
    # staging's own grow path may rebind the arrays (the byte pool is
    # each pass's immutable joined buffer, so it needs no policy)
    "ggrs_tpu/network/pump.py": FencePolicy(
        protected=frozenset({"offs", "lens", "staging"}),
        allowed=frozenset({
            ("PumpStaging", "__init__"),
            ("PumpStaging", "ensure"),
            ("WirePump", "__init__"),
        }),
    ),
    # the vectorized protocol plane's fleet arrays (endpoint_batch.py):
    # the column dict, row->endpoint/emit tables and allocator state are
    # shared mutable state every pump pass reads through live views
    # (_FleetRow, bound _SignalDeques) — only the fleet's declared
    # alloc/adopt/retire entry points may rebind them; the vectorized
    # pass derives masks into locals and writes cells through the shared
    # dict, never rebinding fleet storage
    "ggrs_tpu/network/endpoint_batch.py": FencePolicy(
        protected=frozenset({
            "cols", "eps", "emits", "top", "cap", "free_blocks",
        }),
        allowed=frozenset({
            ("EndpointFleet", "__init__"),
            ("EndpointFleet", "_grow"),
            ("EndpointFleet", "_alloc"),
            ("EndpointFleet", "adopt"),
            ("EndpointFleet", "retire_session"),
        }),
    ),
    # trained model tables are frozen at construction — every lane of
    # every host drafting from version N must read the SAME numbers, so
    # only ModelTables.__init__ may bind the buffers (and the trainer
    # builds NEW tables rather than editing served ones); the hazard
    # cache is derived there once and must never drift from the counts
    "ggrs_tpu/learn/model.py": FencePolicy(
        protected=frozenset({
            "vocab", "switch", "total", "trans", "support",
            "_hazard", "_vocab_bytes", "_vindex",
        }),
        allowed=frozenset({
            ("ModelTables", "__init__"),
        }),
    ),
}


def _is_allowed(node: ast.AST, policy: FencePolicy) -> bool:
    """Walk enclosing (class, method) scopes against the allowlist."""
    qual = qualname_of(node)
    parts = qual.split(".")
    for i in range(len(parts) - 1):
        if (parts[i], parts[i + 1]) in policy.allowed:
            return True
    # module-level code (e.g. constants) never mutates live state
    return qual == "<module>"


def _attrs_of_write(node: ast.AST) -> List[ast.Attribute]:
    """Every Attribute being written by this node, tuple-unpacking
    included — `self.rings, self.states, his, los = fn(...)` is the
    codebase's canonical write form for the stacked worlds, so a pass
    that only saw bare Attribute targets would miss exactly the writes
    it exists to police."""
    if not isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        return []
    targets = (
        node.targets if isinstance(node, ast.Assign) else [node.target]
    )
    out: List[ast.Attribute] = []
    stack = list(targets)
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
        elif isinstance(t, ast.Starred):
            stack.append(t.value)
        elif isinstance(t, ast.Attribute):
            # x.attr = ... | x.attr += ...
            out.append(t)
        elif isinstance(t, ast.Subscript) and isinstance(t.value, ast.Attribute):
            # x.attr[k] = ...
            out.append(t.value)
    return out


def _attr_of_mutating_call(node: ast.Call) -> Optional[ast.Attribute]:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _MUTATORS:
        if isinstance(f.value, ast.Attribute):
            return f.value
    return None


def run(repo: Repo) -> List[Finding]:
    out: List[Finding] = []
    for path, policy in sorted(POLICIES.items()):
        if not repo.exists(path):
            continue
        tree = repo.tree(path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                mut = _attr_of_mutating_call(node)
                attrs = [mut] if mut is not None else []
            else:
                attrs = _attrs_of_write(node)
            for attr in attrs:
                if attr.attr not in policy.protected:
                    continue
                if _is_allowed(node, policy):
                    continue
                out.append(finding(
                    "FEN001", path, node,
                    f"write to device-core state '.{attr.attr}' outside "
                    "the fence/dispatch entry points — route it through "
                    "the owning method (see analysis/fence.py POLICIES)",
                ))
    return out

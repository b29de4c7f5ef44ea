"""Device-safety rules: host-buffer aliasing and collective discipline.

no-aliasing-upload
    ``jnp.asarray`` is banned in data-plane modules (exec/, storage/,
    distsql/, parallel/). On the CPU backend ``asarray`` can alias an
    aligned numpy buffer zero-copy; the streamed-scan plane reuses its
    page assembly buffers, so an aliased device array silently reads
    the NEXT page's bytes (the PR 3 corruption: exec/stream.py now
    documents the exact trap at its ``_batch_views`` site). ``jnp.array``
    copies, but not before it returns (the copy is a program dispatched
    asynchronously over the handed-over buffer: PR 34 found pages
    corrupted behind it), so it protects a buffer that is written
    LATER, never one rewritten at once: reused page buffers are gone
    from exec/stream.py for that reason. Sites that convert provably
    fresh, never-reused buffers (e.g. the result of ``np.concatenate``)
    carry explicit waivers; everything else must copy.

    Regression note (this PR's sweep): exec/expr.py uploaded statement
    parameters and dictionary-gather LUTs with ``jnp.asarray`` — the
    LUT case aliased the dictionary's LIVE table array, safe only by
    the distant argument that dictionaries are append-only — and
    exec/compile.py did the same for its per-plan scalar bounds; all
    now use ``jnp.array`` so safety is local. The remaining data-plane
    ``asarray`` sites (stream page validity maps, scanplane/distsql
    batch assembly, sort rank tables) are waived with the fresh-buffer
    argument spelled out in place.

collective-discipline
    Multi-device execution must be funneled through the per-mesh FIFO
    dispatcher: XLA's host-platform collectives rendezvous by
    (mesh, program) and deadlock when two executions interleave their
    per-device callbacks (PR 1 hit this with two concurrent pmapped
    queries; PR 10's sub-mesh dispatch re-learned it across disjoint
    device domains — same-mode windows in parallel/mesh.py exist
    because of it). Statically: ``shard_map`` / ``jax.pmap`` may only
    be constructed in parallel/distagg.py (the dispatcher's home), and
    every ``make_distributed_fn(...)`` result must flow into
    ``queued_collective_call`` within the same function — a mesh
    program that escapes the dispatcher is a rendezvous hazard on the
    first concurrent statement.

    Round 15 (multi-host) extension, same rule: the CROSS-HOST
    rendezvous entry points — ``jax.distributed.initialize`` /
    ``jax.distributed.shutdown``, anything under
    ``jax.experimental.multihost_utils``, and
    ``mesh_utils.create_hybrid_device_mesh`` — are sanctioned only in
    parallel/multihost.py. The coordinator client, its KV store, and
    the hybrid ICI+DCN mesh are process-global singletons with strict
    ordering constraints (initialize must precede ANY backend touch;
    shutdown mid-flight aborts every peer via the coordination-service
    heartbeat), so a second entry point anywhere else either
    double-initializes the pod or tears live peers down. Everything
    outside the home goes through the multihost wrappers
    (``init_distributed`` / ``shutdown_distributed`` /
    ``global_mesh``), which are idempotent and teardown-ordered.
"""

from __future__ import annotations

import ast

from .core import Finding, direct_nodes

DATA_PLANE_PREFIXES = (
    "cockroach_tpu/exec/", "cockroach_tpu/storage/",
    "cockroach_tpu/distsql/", "cockroach_tpu/parallel/",
)

# the one module allowed to build collective programs: everything it
# produces is executed on its own _MeshDispatcher FIFO thread
COLLECTIVE_HOME = "cockroach_tpu/parallel/distagg.py"

# the one module allowed to touch the cross-host rendezvous
# (jax.distributed / multihost_utils / create_hybrid_device_mesh):
# its init/shutdown wrappers are idempotent and run registered
# teardowns in LIFO order, so the process-global coordinator client
# has exactly one owner
MULTIHOST_HOME = "cockroach_tpu/parallel/multihost.py"


def _is_jnp_asarray(node: ast.Call, module) -> bool:
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "asarray":
        v = f.value
        if isinstance(v, ast.Name):
            tgt = module.imports.get(v.id, "")
            if v.id == "jnp" or tgt in ("jax.numpy",):
                return True
            if v.id in module.from_imports:
                mod, orig = module.from_imports[v.id]
                return f"{mod}.{orig}" == "jax.numpy"
        if (isinstance(v, ast.Attribute) and v.attr == "numpy"
                and isinstance(v.value, ast.Name) and v.value.id == "jax"):
            return True
    if isinstance(f, ast.Name) and f.id == "asarray":
        return module.from_imports.get("asarray", ("", ""))[0] == "jax.numpy"
    return False


def check_no_aliasing_upload(index) -> list[Finding]:
    rule = "no-aliasing-upload"
    out = []
    for rel, m in index.modules.items():
        if not rel.startswith(DATA_PLANE_PREFIXES):
            continue
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Call) and _is_jnp_asarray(node, m):
                reason = m.waiver_for(rule, node.lineno, node.end_lineno)
                out.append(Finding(
                    rule, rel, node.lineno,
                    "jnp.asarray can alias a host buffer zero-copy; "
                    "data-plane page buffers are reused, so use "
                    "jnp.array (copies) or waive with the fresh-buffer "
                    "argument",
                    waived=reason is not None,
                    waiver_reason=reason or ""))
    return out


def _collective_ctor_name(node: ast.Call) -> str | None:
    f = node.func
    name = None
    if isinstance(f, ast.Name):
        name = f.id
    elif isinstance(f, ast.Attribute):
        name = f.attr
    if name in ("shard_map", "pmap"):
        return name
    return None


def _dotted_name(f) -> list[str]:
    """Attribute chain as parts (["jax", "distributed", "initialize"]);
    empty when the chain does not bottom out at a plain Name."""
    parts = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if not isinstance(f, ast.Name):
        return []
    parts.append(f.id)
    parts.reverse()
    return parts


def _multihost_entry_name(node: ast.Call, module) -> str | None:
    """A cross-host rendezvous entry point, or None.

    Matches jax.distributed.{initialize,shutdown} (also via
    ``from jax import distributed``), any call through a
    ``multihost_utils`` segment, and ``create_hybrid_device_mesh``
    under any spelling (the same pragmatic name-matching as the
    shard_map/pmap check: aliasing these to evade the lint would
    itself be a finding in review)."""
    f = node.func
    if isinstance(f, ast.Name):
        if f.id == "create_hybrid_device_mesh":
            return f.id
        if f.id in ("initialize", "shutdown"):
            mod, orig = module.from_imports.get(f.id, ("", ""))
            if mod == "jax.distributed":
                return f"jax.distributed.{orig}"
        return None
    parts = _dotted_name(f)
    if not parts:
        return None
    dotted = ".".join(parts)
    if parts[-1] == "create_hybrid_device_mesh":
        return dotted
    if "multihost_utils" in parts[:-1]:
        return dotted
    if parts[-1] in ("initialize", "shutdown") and len(parts) >= 2 \
            and parts[-2] == "distributed":
        return dotted
    return None


def check_collective_discipline(index) -> list[Finding]:
    rule = "collective-discipline"
    out = []
    for rel, m in index.modules.items():
        if rel == COLLECTIVE_HOME or not rel.startswith("cockroach_tpu/"):
            continue
        # (a) raw collective constructors outside the dispatcher's
        # home; (c) cross-host rendezvous entry points outside the
        # multihost home (same walk, same rule bit)
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Call):
                name = _collective_ctor_name(node)
                if name is not None:
                    reason = m.waiver_for(rule, node.lineno,
                                          node.end_lineno)
                    out.append(Finding(
                        rule, rel, node.lineno,
                        f"{name} constructed outside "
                        f"{COLLECTIVE_HOME}: collective programs must "
                        "be built and executed via the queued "
                        "_MeshDispatcher or concurrent statements "
                        "deadlock the XLA rendezvous",
                        waived=reason is not None,
                        waiver_reason=reason or ""))
                    continue
                if rel == MULTIHOST_HOME:
                    continue
                name = _multihost_entry_name(node, m)
                if name is not None:
                    reason = m.waiver_for(rule, node.lineno,
                                          node.end_lineno)
                    out.append(Finding(
                        rule, rel, node.lineno,
                        f"{name} called outside {MULTIHOST_HOME}: the "
                        "cross-host rendezvous (coordinator client, "
                        "KV store, hybrid mesh) is a process-global "
                        "singleton — a second entry point double-"
                        "initializes the pod or tears live peers "
                        "down; use the multihost wrappers "
                        "(init_distributed / shutdown_distributed / "
                        "global_mesh)",
                        waived=reason is not None,
                        waiver_reason=reason or ""))
        # (b) make_distributed_fn results must flow into
        # queued_collective_call within the same function
        for fi in m.functions.values():
            disciplined: set[int] = set()   # id() of blessed Call nodes
            bound: dict[str, list[ast.Call]] = {}
            nodes = direct_nodes(fi.node)
            calls = [n for n in nodes if isinstance(n, ast.Call)]

            def _name_of(c: ast.Call) -> str | None:
                f = c.func
                if isinstance(f, ast.Name):
                    return f.id
                if isinstance(f, ast.Attribute):
                    return f.attr
                return None

            mdf_calls = [c for c in calls
                         if _name_of(c) == "make_distributed_fn"]
            if not mdf_calls:
                continue
            for n in nodes:
                if (isinstance(n, ast.Assign) and len(n.targets) == 1
                        and isinstance(n.targets[0], ast.Name)):
                    hits = [c for c in ast.walk(n.value)
                            if isinstance(c, ast.Call) and c in mdf_calls]
                    if hits:
                        bound.setdefault(n.targets[0].id, []).extend(hits)
            for c in calls:
                if _name_of(c) != "queued_collective_call":
                    continue
                for sub in ast.walk(c):
                    if isinstance(sub, ast.Call) and sub in mdf_calls:
                        disciplined.add(id(sub))
                    if isinstance(sub, ast.Name) and sub.id in bound:
                        for h in bound[sub.id]:
                            disciplined.add(id(h))
            for c in mdf_calls:
                if id(c) in disciplined:
                    continue
                reason = m.waiver_for(rule, c.lineno, c.end_lineno)
                out.append(Finding(
                    rule, rel, c.lineno,
                    "make_distributed_fn result does not flow into "
                    "queued_collective_call in this function: the "
                    "compiled mesh program would execute outside the "
                    "per-mesh FIFO dispatcher (rendezvous-deadlock "
                    "hazard under concurrency)",
                    waived=reason is not None,
                    waiver_reason=reason or ""))
    return out

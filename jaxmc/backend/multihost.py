r"""Multi-host (DCN) distributed BFS — SURVEY.md §2.3/§5 "distributed
communication backend".

The single-controller MeshExplorer shards over the devices of ONE
process. This module runs the SAME sharded level step (mesh.py
_get_mesh_step — compiled kernels, gather exchange by default — this
fixed-capacity loop cannot re-run a level on an a2a bucket overflow,
JAXMC_MESH_EXCHANGE overrides — fp128
hash-partitioned seen shards, psum'd totals) over a mesh that spans
SEVERAL jax processes, the way a TPU pod spans hosts: each process
contributes its local devices, `jax.distributed.initialize` wires the
coordinator, and the collectives ride the inter-process transport (Gloo
on CPU here; ICI/DCN on real pods — the program is identical, which is
the point of jax's multi-controller model).

Multi-controller discipline: every process executes the same host loop;
device data lives in global arrays built with
`jax.make_array_from_callback`; the host reads ONLY replicated psum'd
scalars (via its own addressable shard). The frontier keeps a FIXED
per-device capacity (the step's out_cap variant) so no process ever
needs another host's rows between levels; outgrowing it aborts loudly
with a replicated flag.

Validated end to end on this box by dryrun_multihost
(__graft_entry__.py): 2 processes x 4 virtual CPU devices run the FULL
reference-raft MCraftMicro model to completion with the pinned counts
(6185 generated / 694 distinct), exercising the same code path a
multi-host pod would (VERDICT r3 #7; ROADMAP gap 6).
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _local_scalar(arr) -> int:
    """Read a replicated (psum'd) per-device scalar from MY addressable
    shard — np.asarray(global_array) is illegal for non-addressable
    multi-process arrays."""
    import numpy as np
    return int(np.asarray(arr.addressable_shards[0].data).reshape(-1)[0])


def run_multihost_child(process_id: int, num_processes: int,
                        coordinator: str, local_devices: int = 4,
                        spec: str = None, cfg: str = None,
                        FC: int = 256, SC: int = 4096,
                        max_levels: int = 200,
                        store_trace: bool = True):
    """One process of the multi-host run. MUST be called before any other
    jax initialization in the process. Returns (generated, distinct,
    violation) — identical on every process (psum'd totals + the same
    gathered trace); violation is None for a clean run, else
    (kind, name, trace) with trace = [(state, action-label), ...], the
    exact counterexample the single-chip MeshExplorer produces for the
    same model over the same global device count (trace contract:
    /root/reference/README.md:268-318)."""
    import re
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags.strip() +
        f" --xla_force_host_platform_device_count={local_devices}")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..sem.modules import Loader, bind_model
    from ..front.cfg import parse_cfg
    from .mesh import MeshExplorer

    devs = jax.devices()  # GLOBAL devices, across all processes
    D = len(devs)
    assert D == num_processes * local_devices, (D, num_processes)
    mesh = Mesh(np.array(devs), ("d",))

    spec = spec or os.path.join(_REPO, "specs", "MCraftMicro.tla")
    cfg = cfg or os.path.join(_REPO, "specs", "MCraft_micro.cfg")
    # the MC shims EXTEND specs that live in the reference checkout;
    # its location is machine-specific, so take it from the environment
    # rather than hardcoding this dev box's path
    ref_root = os.environ.get("JAXMC_REFERENCE_ROOT", "/root/reference")
    ref_examples = os.path.join(ref_root, "examples")
    search = [os.path.dirname(spec)]
    if os.path.isdir(ref_examples):
        search.append(ref_examples)
    model = bind_model(
        Loader(search).load_path(spec),
        parse_cfg(open(cfg).read()))

    # the compile pipeline is process-local and deterministic: both
    # processes build byte-identical kernels and step programs.
    # Exchange stays GATHER here even though a2a is the D>1 default
    # (ISSUE 8): this fixed-capacity multi-controller loop cannot
    # re-run a level, so an a2a bucket+spill overflow would abort a
    # run the gather exchange completes — JAXMC_MESH_EXCHANGE still
    # overrides for pods whose skew envelope is known.
    exchange = os.environ.get("JAXMC_MESH_EXCHANGE", "").strip() \
        or "gather"
    me = MeshExplorer(model, mesh=mesh, store_trace=False,
                      exchange=exchange)
    W, K = me.W, me.K

    # init states: identical host computation on every process (the
    # shard construction is shared with MeshExplorer.run — one layout
    # rule for host and device dedup)
    from .bfs import filter_init_states
    init_rows = np.stack([me.layout.encode(st) for st in me.init_states])
    explored, viol = filter_init_states(model, me.layout, init_rows)
    assert viol is None, "initial-state violation in the dryrun model"
    # per-shard seen occupancy (ISSUE 10): the step's merge takes the
    # valid-prefix length explicitly (the rank merge binary-searches
    # it), so the loop carries the step's seen-count output back into
    # the next level's input, seeded by the counts _init_shards built
    seen_h, front_h, fcount_h, scount_h = me._init_shards(
        init_rows, explored, D, SC, FC)

    def dist(h):
        sh = NamedSharding(mesh, P("d"))
        return jax.make_array_from_callback(
            h.shape, sh, lambda idx: h[idx])

    seen = dist(seen_h)
    seen_cnt = dist(scount_h)
    frontier, fcount = dist(front_h), dist(fcount_h)

    generated = len(init_rows)
    distinct = len(explored)
    step = me._get_mesh_step(SC, FC, out_cap=FC)
    depth = 0

    # ---- trace recording (VERDICT r4 #7): every process records ONLY
    # its own devices' frontier/provenance shards per level; on a
    # violation the full per-level arrays are reassembled with a
    # process_allgather PULL (the "gather protocol") and every process
    # independently walks the same provenance chain the single-chip
    # MeshExplorer walks (mesh.py _mesh_trace_to), producing the exact
    # same counterexample trace. Level 0 is the init frontier, which
    # every process computed identically on the host.
    from .bfs import SENTINEL

    def _partials(garr, fill, dtype):
        """(partial-full-array, ownership-mask) from MY addressable
        shards of a [D, ...]-sharded global array."""
        part = np.full(garr.shape, fill, dtype)
        mask = np.zeros(garr.shape[0], bool)
        for sh in garr.addressable_shards:
            part[sh.index] = np.asarray(sh.data)
            mask[sh.index[0]] = True
        return part, mask

    def _gather_full(part, mask):
        from jax.experimental import multihost_utils as mhu
        parts = np.asarray(mhu.process_allgather(part))
        masks = np.asarray(mhu.process_allgather(mask))
        out = part.copy()
        for pi in range(parts.shape[0]):
            out[masks[pi]] = parts[pi][masks[pi]]
        return out

    levels = [(front_h, None, np.ones(D, bool))] if store_trace else None

    def _assemble_trace(dev, slot, lvl, extra=None):
        full = []
        for rows_p, src_p, mask in levels[:lvl + 1]:
            if mask.all():
                full.append((rows_p, src_p))
            else:
                full.append((_gather_full(rows_p, mask),
                             _gather_full(src_p, mask)
                             if src_p is not None else None))
        out = []
        d, i = dev, slot
        C = me.A * FC
        for lv in range(lvl, -1, -1):
            rows, src = full[lv]
            st = me.layout.decode_packed(np.asarray(rows[d][i]))
            if lv == 0:
                out.append((st, "Initial predicate"))
            else:
                g = int(src[d][i])
                a = (g % C) // FC
                out.append((st, me.labels_flat[a]))
                d, i = g // C, (g % C) % FC
        out.reverse()
        if extra is not None:
            out.append(extra)
        return out

    def _first_bad_device(per_dev_partial, mask, pred):
        full = _gather_full(per_dev_partial, mask)
        for d in range(D):
            if pred(full[d]):
                return d, full
        return None, full

    while depth < max_levels:
        outs = step(seen, seen_cnt, frontier, fcount)
        (seen, seen_cnt, frontier, fcount, tot_gen, tot_new,
         any_ovf, tot_front, fixed_ovf, any_inv, any_dead,
         any_assert) = outs[:12]
        # index 20 is the psum'd a2a spill-row count (ISSUE 8): rows
        # drained by the second all_to_all pass instead of aborting
        (front_src, inv_which, inv_slot, dead_local, dead_slot,
         assert_bad, asrt_a, asrt_f) = outs[12:20]
        ovc = _local_scalar(any_ovf)  # 0 = none, else max kernel2.OV_*
        if ovc:
            from ..compile.kernel2 import OV_DEMOTED, OV_PACK
            if ovc == OV_DEMOTED:
                raise RuntimeError(
                    "a demoted compile-recovery fired in the multi-host "
                    "run (kernel under-approximates here): run the "
                    "host_seen mode — raising caps cannot help")
            if ovc == OV_PACK:
                raise RuntimeError(
                    "a value escaped its bit-packed lane's profiled "
                    "range in the multi-host run: deepen sampling or "
                    "rerun with JAXMC_PACK=0")
            raise RuntimeError("kernel capacity overflow in the "
                               "multi-host run")
        if _local_scalar(fixed_ovf):
            raise RuntimeError(
                f"fixed shard capacity exceeded (FC={FC}, SC={SC}): "
                f"raise them for this model")
        if store_trace:
            rows_p, mask = _partials(frontier, SENTINEL, np.int32)
            src_p, _ = _partials(front_src, -1, np.int32)
            levels.append((rows_p, src_p, mask))
        # violation precedence mirrors the single-chip MeshExplorer host
        # loop EXACTLY (mesh.py: deadlock -> assert -> invariant) so a
        # level with simultaneous violations yields the same verdict and
        # the same counterexample on both backends
        if model.check_deadlock and _local_scalar(any_dead):
            if store_trace:
                dl, mk = _partials(dead_local, 0, np.int32)
                ds = _partials(dead_slot, -1, np.int32)[0]
                d, _ = _first_bad_device(dl, mk, lambda x: x != 0)
                ds_f = _gather_full(ds, mk)
                tr = _assemble_trace(d, int(ds_f[d]), depth)
                return generated, distinct, ("deadlock", "deadlock", tr)
            raise RuntimeError("deadlock in the dryrun model")
        if _local_scalar(any_assert):
            # assert fires while EXPANDING the current frontier (level
            # `depth`): provenance is (action instance, frontier slot)
            if store_trace:
                ab, mk = _partials(assert_bad, 0, np.int32)
                am = _partials(asrt_a, -1, np.int32)[0]
                af = _partials(asrt_f, -1, np.int32)[0]
                d, ab_full = _first_bad_device(ab, mk, lambda x: x != 0)
                am_f = _gather_full(am, mk)
                af_f = _gather_full(af, mk)
                tr = _assemble_trace(d, int(af_f[d]), depth)
                nm = f"assertion in {me.labels_flat[int(am_f[d])]}"
                return generated, distinct, ("assert", nm, tr)
            raise RuntimeError("Assert violation in the dryrun model")
        if _local_scalar(any_inv):
            # invariant violations live in the NEW frontier (depth+1).
            # Selection mirrors mesh.py: the globally LOWEST violated
            # cfg-invariant index wins, then the first device holding it
            if store_trace:
                from .mesh import _BIG
                iw, mk = _partials(inv_which, int(_BIG), np.int32)
                isl = _partials(inv_slot, -1, np.int32)[0]
                iw_full = _gather_full(iw, mk)
                which = int(iw_full.min())
                d = int(np.argmax(iw_full == which))
                isl_f = _gather_full(isl, mk)
                nm = me.inv_fns[which][0]
                tr = _assemble_trace(d, int(isl_f[d]), depth + 1)
                return generated, distinct, ("invariant", nm, tr)
            raise RuntimeError("invariant violation in the dryrun model")
        generated += _local_scalar(tot_gen)
        distinct += _local_scalar(tot_new)
        depth += 1
        if _local_scalar(tot_front) == 0:
            return generated, distinct, None
    raise RuntimeError(f"did not converge in {max_levels} levels")


def fmt_trace_line(i, st, label) -> str:
    """One parseable line per trace step: deterministic state rendering
    (sorted vars, sem.values.fmt) so parent processes and tests compare
    multi-host traces against single-chip ones textually."""
    from ..sem.values import fmt
    body = " /\\ ".join(f"{v} = {fmt(st[v])}" for v in sorted(st))
    return f"MHTRACE {i}: [{label}] {body}"


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--coordinator", default="localhost:29521")
    ap.add_argument("--local-devices", type=int, default=4)
    ap.add_argument("--spec", default=None)
    ap.add_argument("--cfg", default=None)
    ap.add_argument("--fc", type=int, default=256)
    ap.add_argument("--sc", type=int, default=4096)
    a = ap.parse_args()
    gen, dist_, viol = run_multihost_child(
        a.process_id, a.num_processes, a.coordinator, a.local_devices,
        spec=a.spec, cfg=a.cfg, FC=a.fc, SC=a.sc)
    if viol is not None:
        kind, name, trace = viol
        print(f"MHVIOLATION p{a.process_id}: {kind} {name} "
              f"({len(trace)} states)", flush=True)
        for i, (st, label) in enumerate(trace):
            print(fmt_trace_line(i, st, label), flush=True)
    print(f"MULTIHOST p{a.process_id}: {gen} generated / "
          f"{dist_} distinct", flush=True)


if __name__ == "__main__":
    main()

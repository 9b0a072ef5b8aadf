"""The phased traced step: the engine's train step, fenceable per phase.

The production step is one fused jit program; nothing inside it is
host-timeable. Under ``--trace`` the Trainer swaps in ``PhasedStep``: the
same math, built from the engine's own pieces (``_make_local_grads``,
``_stage2_rs``, ``_replica_sync``, ``_clip_grads``, ``_apply_updates``),
split into separately-jitted ``shard_map`` segments at exactly the
boundaries the cost model prices (``topo.cost.PHASES``), each run under
``SpanRecorder.fenced``. Segment sum ≈ step wall time by construction (the
acceptance bound); fencing changes XLA's fusion, so traced runs are
float-close, not bitwise, to the seed step — which is why ``--trace`` off
keeps the untouched monolithic step (DESIGN.md §10).

Inter-segment gradient arrays use ``engine._os_spec`` — sharded over **all**
mesh axes — even for primary-layout grads: seed-regime grads are
device-varying over the E/R axes (the deferred hierarchical sync), so any
spec that nominally replicates them would corrupt the round-trip between
segments. Sharding over every axis makes each device's local block travel
untouched.

The in-loop collectives (per-layer weight gathers, stage-1 grad RS) cannot
be fenced — they live inside ``lax.scan``. ``run_probes`` measures them
out-of-band: serial re-executions of each collective over the real stacked
primaries (one per layer, so XLA cannot hoist a loop-invariant gather),
reduced to a scalar so only the collective's cost is timed. Probe spans are
attribution only — they are NOT part of the wall-time sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..core import collectives as col
from ..core.partition import GATHER_Q, MATMUL
from .spans import PROBES, SpanRecorder


class PhasedStep:
    """Fenceable train step for one engine + loss_fn (trace mode only)."""

    def __init__(self, engine, loss_fn, batch_specs):
        self.eng = eng = engine
        cfg = eng.cfg
        self.stream = cfg.stream_grads
        self.names = sorted(eng.specs)
        snames = set(eng.stream_leaf_names()) if self.stream else set()
        # legacy = primary-layout grads (seed path); streamed sinks arrive
        # from the backward already reduced to os layout
        self.legacy = [n for n in self.names if n not in snames]
        self.sink_names = sorted(snames)

        state_specs = eng.state_in_specs()
        # every inter-segment grad leaf: sharded over ALL axes (see module
        # docstring — device-varying blocks must round-trip untouched)
        gspec = {n: eng._os_spec(eng.specs[n]) for n in self.names}
        leg_spec = {n: gspec[n] for n in self.legacy}
        sink_spec = {n: gspec[n] for n in self.sink_names}
        local_grads = eng._make_local_grads(loss_fn)
        stream = self.stream

        def sm(fn, in_specs, out_specs, **jit_kw):
            return jax.jit(shard_map(fn, mesh=eng.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False),
                           **jit_kw)

        # -- segment: fwd_bwd (microbatch loop, grads in diff layout) ------
        def seg_grads(state, batch):
            grads, loss_rep, gtok = local_grads(state["primaries"], batch)
            g_legacy, g_sinks = grads if stream else (grads, {})
            return dict(g_legacy), dict(g_sinks), loss_rep, gtok

        self._grads = sm(seg_grads, (state_specs, batch_specs),
                         (leg_spec, sink_spec, P(), P()))

        # -- segment: grad_rs_e (stage-2 RS over the extra-grad axes) ------
        def seg_stage2(g_legacy):
            return {n: eng._stage2_rs(n, g) for n, g in g_legacy.items()}

        self._stage2 = sm(seg_stage2, (leg_spec,), leg_spec)

        # -- segment: cross_replica (stage-3 replica sync) -----------------
        def seg_cross(g2):
            return {n: eng._replica_sync(n, g) for n, g in g2.items()}

        self._cross = sm(seg_cross, (leg_spec,), leg_spec)

        # -- segment: gnorm_clip -------------------------------------------
        def seg_clip(os_grads):
            return eng._clip_grads(os_grads)

        self._clip = sm(seg_clip, (gspec,), (gspec, P()))

        # -- segment: update (AdamW + update all-gather) -------------------
        def seg_update(state, os_grads):
            return eng._apply_updates(state, os_grads)

        self._update = sm(seg_update, (state_specs, gspec),
                          (state_specs, P()), donate_argnums=(0,))

        # -- out-of-band probes --------------------------------------------
        self._eval = eng.make_eval_step(loss_fn, batch_specs)
        self._build_probes()

    def __call__(self, state, batch, rec: SpanRecorder):
        """One fenced step; same (new_state, metrics) as the seed step."""
        g_leg, g_sink, loss_rep, gtok = rec.fenced(
            "fwd_bwd", self._grads, state, batch)
        if self.legacy:
            g_leg = rec.fenced("grad_rs_e", self._stage2, g_leg)
            g_leg = rec.fenced("cross_replica", self._cross, g_leg)
        os_grads = {n: g_leg[n] if n in g_leg else g_sink[n]
                    for n in self.names}
        os_grads, gnorm = rec.fenced("gnorm_clip", self._clip, os_grads)
        new_state, lr = rec.fenced("update", self._update, state, os_grads)
        metrics = dict(loss=loss_rep, grad_norm=gnorm, lr=lr, tokens=gtok)
        return new_state, metrics

    # -- probes -------------------------------------------------------------

    def _build_probes(self):
        eng = self.eng
        cdt = jnp.dtype(eng.cfg.compute_dtype)
        prim_specs = eng.state_in_specs()["primaries"]
        os_specs = eng.state_in_specs()["master"]
        # stacked leaves with an issue() half: the layer loop's gathers
        self.pf = [n for n in self.names
                   if eng.specs[n].stack and eng.fns[n].issue is not None]
        self.rs_leaves = [n for n in self.names
                          if eng.specs[n].stack
                          and eng.specs[n].kind in (MATMUL, GATHER_Q)]

        def sm(fn, names, specs):
            return jax.jit(shard_map(
                fn, mesh=eng.mesh,
                in_specs=({n: specs[n] for n in names},),
                out_specs=P(), check_vma=False))

        def checksum(tree):
            return sum(jnp.sum(leaf.astype(jnp.float32))
                       for leaf in jax.tree.leaves(tree))

        # fwd_allgather: scan the real per-layer gather issue over the
        # stacked primaries — one collective per layer, each layer's input
        # distinct, so nothing is hoistable or CSE-able
        def probe_fwd_ag(prims):
            total = jnp.zeros((), jnp.float32)
            for n in self.pf:
                def body(c, row, n=n):
                    return c + checksum(eng.fns[n].issue(row)), None
                s, _ = lax.scan(body, jnp.zeros((), jnp.float32), prims[n])
                total = total + s
            return total

        self._p_fwd_ag = sm(probe_fwd_ag, self.pf, prim_specs) \
            if self.pf else None

        # bwd_allgather: the backward re-materialization. With a secondary
        # partition, gather the wire-format secondary shards (synthesized
        # per layer from the real primary row — values are irrelevant to
        # timing, per-layer variation defeats CSE); without one the
        # backward re-runs the primary gather, so reuse the issue probe.
        def probe_bwd_ag(prims):
            total = jnp.zeros((), jnp.float32)
            for n in self.rs_leaves:
                lcfg = eng.leaf_cfg[n]
                if lcfg.axes.secondary is None:
                    if eng.fns[n].issue is None:
                        continue

                    def body(c, row, n=n):
                        return c + checksum(eng.fns[n].issue(row)), None
                else:
                    pad = eng._pad[n]
                    sec_len = pad // lcfg.sec_degree
                    n_scales = pad // lcfg.quant_block // lcfg.sec_degree

                    def body(c, row, lcfg=lcfg, sec_len=sec_len,
                             n_scales=n_scales):
                        base = row.astype(jnp.float32)
                        sq = jnp.resize(base, (sec_len,)).astype(jnp.int8)
                        ss = jnp.abs(jnp.resize(base, (n_scales,))) + 1.0
                        out = col.gather_secondary_q(
                            sq, ss, lcfg.axes.secondary, lcfg)
                        return c + checksum(out), None
                s, _ = lax.scan(body, jnp.zeros((), jnp.float32), prims[n])
                total = total + s
            return total

        self._p_bwd_ag = sm(probe_bwd_ag, self.rs_leaves, prim_specs) \
            if self.rs_leaves else None

        # grad_rs_w: stage-1 dense-grad reduce-scatter over the W axes, one
        # per layer per backward — dense row synthesized from the primary
        def probe_grs_w(prims):
            total = jnp.zeros((), jnp.float32)
            for n in self.rs_leaves:
                lcfg = eng.leaf_cfg[n]
                pad = eng._pad[n]

                def body(c, row, lcfg=lcfg, pad=pad):
                    g = jnp.resize(row.astype(jnp.float32), (pad,))
                    out = col.reduce_scatter_flat(g, lcfg.axes.weight, lcfg)
                    return c + jnp.sum(out), None
                s, _ = lax.scan(body, jnp.zeros((), jnp.float32), prims[n])
                total = total + s
            return total

        self._p_grs_w = sm(probe_grs_w, self.rs_leaves, prim_specs) \
            if self.rs_leaves else None

        # update_gather: the real per-leaf update all-gather over E+R
        def probe_upd(master):
            return sum(
                (checksum(col.update_all_gather(master[n],
                                                eng.leaf_cfg[n], cdt))
                 for n in self.names),
                jnp.zeros((), jnp.float32))

        self._p_upd = sm(probe_upd, self.names, os_specs)

    def run_probes(self, state, batch, rec: SpanRecorder):
        """Out-of-band comm attribution: serial re-execution of each
        collective family, fenced individually. Records one span per probe
        (NOT summed into the wall-time budget)."""
        prim = state["primaries"]
        rec.fenced("fwd", self._eval, state, batch)
        if self._p_fwd_ag is not None:
            rec.fenced("fwd_allgather", self._p_fwd_ag,
                       {n: prim[n] for n in self.pf})
        if self._p_bwd_ag is not None:
            rec.fenced("bwd_allgather", self._p_bwd_ag,
                       {n: prim[n] for n in self.rs_leaves})
        if self._p_grs_w is not None:
            rec.fenced("grad_rs_w", self._p_grs_w,
                       {n: prim[n] for n in self.rs_leaves})
        rec.fenced("update_gather", self._p_upd, state["master"])

    def probe_inventory(self) -> dict:
        """Deterministic description of what the probes execute — gated in
        BENCH_obs.json (structure, never wall-clock)."""
        eng = self.eng
        layers = {n: int(eng.specs[n].stack or 0) for n in self.rs_leaves}
        return dict(
            fwd_allgather=dict(leaves=list(self.pf),
                               layers=sum(layers.get(n, 0)
                                          for n in self.pf)),
            bwd_allgather=dict(
                leaves=list(self.rs_leaves),
                secondary=[n for n in self.rs_leaves
                           if eng.leaf_cfg[n].axes.secondary is not None]),
            grad_rs_w=dict(leaves=list(self.rs_leaves),
                           layers=sum(layers.values())),
            update_gather=dict(leaves=list(self.names)),
        )

    # -- measured phase attribution -----------------------------------------

    def phase_seconds(self, rec: SpanRecorder, step: int,
                      probe: dict[str, float] | None = None) -> dict:
        """Map one step's fenced segments (+ the latest probe measurements)
        onto the cost model's phase names (``topo.cost.PHASES``) plus
        ``compute``. In-loop probes measure one microbatch's collectives, so
        they scale by n_microbatch; ``compute`` is the fwd_bwd segment minus
        the in-loop comm estimate (floored at 0 — on overlap schedules the
        comm is partially hidden inside that same segment)."""
        seg = rec.step_seconds(step)
        probe = probe if probe is not None else self.last_probe(rec)
        n_mb = self.eng.hp.n_microbatch
        out = {}
        for ph in ("fwd_allgather", "bwd_allgather", "grad_rs_w"):
            out[ph] = n_mb * probe.get(ph, 0.0)
        out["grad_rs_e"] = seg.get("grad_rs_e", 0.0)
        out["cross_replica"] = seg.get("cross_replica", 0.0)
        # the update segment is AdamW + gather; the probe isolates the
        # gather share when available, capped by the measured segment
        upd_seg = seg.get("update", 0.0)
        out["update_gather"] = min(probe["update_gather"], upd_seg) \
            if "update_gather" in probe else upd_seg
        in_loop = sum(out[ph] for ph in
                      ("fwd_allgather", "bwd_allgather", "grad_rs_w"))
        out["compute"] = max(seg.get("fwd_bwd", 0.0) - in_loop, 0.0)
        return out

    def last_probe(self, rec: SpanRecorder) -> dict[str, float]:
        """Most recent measurement of each probe span, any step."""
        out: dict[str, float] = {}
        for s in rec.spans:
            if s.name in PROBES:
                out[s.name] = s.dur
        return out

    def overlap_efficiency(self, rec: SpanRecorder, step: int) -> float:
        """Fraction of measured comm time that sits in the *overlappable*
        in-loop region rather than the structurally-serial post-backward
        tail. Measurement-only (no model input); the calibrate CLI's A/B
        run measures how much of the in-loop share is actually hidden."""
        ph = self.phase_seconds(rec, step)
        hideable = (ph["fwd_allgather"] + ph["bwd_allgather"]
                    + ph["grad_rs_w"])
        exposed = ph["grad_rs_e"] + ph["cross_replica"] + ph["update_gather"]
        total = hideable + exposed
        return hideable / total if total > 0 else 0.0

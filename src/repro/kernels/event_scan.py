"""Pallas admission/completion scan for the event-model dispatcher.

The inner loop of :class:`repro.core.refine._FastEventSim` — admit the
head kernel's blocks round-robin first-fit, advance time to the next
cohort retirement, repeat — is a per-candidate sequential scan with a
small fixed-shape state (per-unit ``used``/residency plus
``max_resident`` cohort slots per unit).  That shape is exactly what an
accelerator wants: grid over the B candidate orders, each program
walking its own order row with the state resident in VMEM, the shared
kernel table broadcast to every program.  One dispatch then scores a
whole move batch — the dispatch-discipline requirement (see
``repro.core.batched``) that makes device-side scheduling pay for its
launch.

Three pieces, same float32 arithmetic:

* :func:`event_scan_core` — the scan over one order row as a pure jax
  function (``lax.while_loop`` over events, per-block admission with
  the reference's same-instant cohort merge).
* :func:`event_times_jax` — ``jit(vmap(core))`` over the batch; the
  kernel table is broadcast (``in_axes=None``).
* :func:`event_times_pallas` — ``pl.pallas_call`` with ``grid=(B,)``,
  one ``(1, n)`` order row per program and broadcast table operands;
  ``interpret=True`` (the default off-TPU) runs the same kernel on CPU
  for tier-1 tests; the compiled path is tested only where JAX runs
  on an accelerator.  The TPU compiler refuses it as written: its
  ``(1, n)`` row block is not (8, 128)-aligned.

float32 deviations from the float64 reference, all documented and
property-tested (``tests/test_batched.py``):

* admission slack — the reference admits on ``used + dem <= cap +
  1e-12``; in float32 the accumulated ``used`` carries ~1e-7 relative
  rounding, so the scan uses ``cap * F32_FIT_RTOL`` slack instead,
  sized well below any per-block demand (which is what real rejections
  are measured in) but above float32 accumulation noise, keeping
  admission *decisions* identical to the reference's.
* retirement threshold — the reference retires a cohort at
  ``frac <= 1e-9``; float32 cannot resolve 1e-9 against O(1) block
  fractions, so the scan retires at ``frac <= 1e-6`` (still below any
  modelled work quantum).
* times — event instants accumulate float32 rounding over O(n) events;
  :data:`F32_EVENT_RTOL` bounds the relative error vs the reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

try:  # pragma: no cover - exercised implicitly by import
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    HAS_JAX = True
except Exception:  # pragma: no cover
    jax = jnp = pl = None
    HAS_JAX = False

__all__ = ["HAS_JAX", "F32_EVENT_RTOL", "F32_FIT_RTOL", "EventScanConfig",
           "config_for_device", "event_scan_core", "event_times_jax",
           "event_times_pallas", "event_times_reference"]

#: relative tolerance of float32 scan times vs the float64
#: ``_FastEventSim`` (audited in tests; observed error is ~1e-6).
F32_EVENT_RTOL = 5e-4

#: admission slack as a fraction of each capacity (see module docstring).
F32_FIT_RTOL = 1e-5

#: float32 retirement threshold (reference: 1e-9 in float64).
_RETIRE_EPS = 1e-6

_EPS = 1e-12


class EventScanConfig(NamedTuple):
    """Static (hashable) device geometry for the scan."""

    caps: tuple          # per-dim capacities, device.caps order
    n_units: int
    max_resident: int
    sat_idx: int         # index of sat_dim in caps order, -1 if absent
    compute_rate: float
    mem_bw: float
    sat_compute: float
    sat_memory: float


def config_for_device(device) -> EventScanConfig:
    dims = tuple(device.caps)
    return EventScanConfig(
        caps=tuple(device.cap(d) for d in dims),
        n_units=int(device.n_units),
        max_resident=int(device.max_resident),
        sat_idx=(dims.index(device.sat_dim)
                 if device.sat_dim in dims else -1),
        compute_rate=float(device.compute_rate),
        mem_bw=float(device.mem_bw),
        sat_compute=float(device.sat_compute),
        sat_memory=float(device.sat_memory),
    )


def event_scan_core(row, nbk, dem, inst_b, mem_b, caps,
                    cfg: EventScanConfig):
    """Event-model makespan of one order ``row`` ((n,) int32 indices
    into the kernel table) — float32, pure jax, shape-static.

    ``caps`` is the (D,) float32 capacity vector, passed as an operand
    (not closed over) so the same body traces as a Pallas kernel.

    Mirrors ``_FastEventSim.simulate`` from a fresh start: per-block
    cyclic first-fit admission from the round-robin pointer with
    same-instant cohort merge, rate recompute from cohort work sums,
    completion events at ``min(frac / lam)``, oversized heads draining
    alone in ``ceil(blocks / n_units)`` solo passes.
    """
    n = row.shape[0]
    U, C = cfg.n_units, max(cfg.max_resident, 1)
    D = len(cfg.caps)
    fit_slack = caps * F32_FIT_RTOL + _EPS
    max_res = cfg.max_resident
    f32 = jnp.float32

    def rates(used, ckn, cnb, cin, cmb):
        occ_m = cnb > 0
        sum_c = jnp.sum(cin * cnb.astype(f32), axis=1)      # (U,)
        sum_m = jnp.sum(cmb * cnb.astype(f32), axis=1)
        if cfg.sat_idx >= 0:
            occ = used[:, cfg.sat_idx]
            eff_c = jnp.maximum(jnp.minimum(1.0, occ / cfg.sat_compute),
                                _EPS)
            eff_m = jnp.maximum(jnp.minimum(1.0, occ / cfg.sat_memory),
                                _EPS)
        else:
            eff_c = eff_m = jnp.ones((U,), f32)
        lam = jnp.minimum(
            cfg.compute_rate * eff_c / jnp.maximum(sum_c, _EPS),
            cfg.mem_bw * eff_m / jnp.maximum(sum_m, _EPS))
        return jnp.where(occ_m.any(axis=1), lam, 0.0)

    # state: t, head, bleft, rr, used (U,D), nres (U,),
    # ckn/cnb (U,C) int32, cfr/cta/cin/cmb (U,C) f32.
    def admit_one(s):
        """Place one block of the head kernel (cond guarantees fit)."""
        (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
         cin, cmb) = s
        kid = row[jnp.minimum(head, n - 1)]
        dk = dem[kid]                                        # (D,)
        fits = ((nres + 1 <= max_res) &
                jnp.all(used + dk[None, :] <= caps[None, :] +
                        fit_slack[None, :], axis=1))         # (U,)
        off = (jnp.arange(U, dtype=jnp.int32) - rr) % U
        u = jnp.argmin(jnp.where(fits, off, U).astype(jnp.int32))
        used = used.at[u].add(dk)
        nres = nres.at[u].add(1)
        # same-instant cohort merge: a (kernel, instant) cohort is
        # unique per unit, so at most one slot matches.
        match = (cnb[u] > 0) & (ckn[u] == kid) & (cta[u] == t)
        slot = jnp.where(match.any(), jnp.argmax(match),
                         jnp.argmin(cnb[u] > 0))             # first free
        cnb = cnb.at[u, slot].add(1)
        ckn = ckn.at[u, slot].set(kid)
        cfr = cfr.at[u, slot].set(jnp.where(match.any(), cfr[u, slot],
                                            f32(1.0)))
        cta = cta.at[u, slot].set(t)
        cin = cin.at[u, slot].set(inst_b[kid])
        cmb = cmb.at[u, slot].set(mem_b[kid])
        rr = (u.astype(jnp.int32) + 1) % U
        bleft = bleft - 1
        adv = bleft == 0
        head = head + jnp.where(adv, 1, 0)
        nxt = row[jnp.minimum(head, n - 1)]
        bleft = jnp.where(adv & (head < n), nbk[nxt], bleft)
        return (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
                cin, cmb)

    def can_admit(s):
        (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
         cin, cmb) = s
        kid = row[jnp.minimum(head, n - 1)]
        dk = dem[kid]
        fits = ((nres + 1 <= max_res) &
                jnp.all(used + dk[None, :] <= caps[None, :] +
                        fit_slack[None, :], axis=1))
        return (head < n) & fits.any()

    def step(s):
        s = jax.lax.while_loop(can_admit, admit_one, s)
        (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
         cin, cmb) = s
        nres_tot = nres.sum()

        def oversized(s):
            (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
             cin, cmb) = s
            kid = row[jnp.minimum(head, n - 1)]
            occ = dem[kid, cfg.sat_idx] if cfg.sat_idx >= 0 else f32(0.0)
            eff_c = jnp.maximum(jnp.minimum(1.0, occ / cfg.sat_compute),
                                _EPS) if cfg.sat_idx >= 0 else f32(1.0)
            eff_m = jnp.maximum(jnp.minimum(1.0, occ / cfg.sat_memory),
                                _EPS) if cfg.sat_idx >= 0 else f32(1.0)
            t1 = jnp.maximum(inst_b[kid] / (cfg.compute_rate * eff_c),
                             mem_b[kid] / (cfg.mem_bw * eff_m))
            passes = jnp.ceil(bleft.astype(f32) / U).astype(jnp.int32)
            t = t + passes.astype(f32) * t1
            head = head + 1
            nxt = row[jnp.minimum(head, n - 1)]
            bleft = jnp.where(head < n, nbk[nxt], bleft)
            return (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
                    cin, cmb)

        def complete(s):
            (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
             cin, cmb) = s
            lam = rates(used, ckn, cnb, cin, cmb)            # (U,)
            occ_m = cnb > 0
            ttf = jnp.where(occ_m, cfr / lam[:, None], jnp.inf)
            dt = ttf.min()
            t = t + dt
            cfr = jnp.where(occ_m, cfr - lam[:, None] * dt, cfr)
            fin = occ_m & (cfr <= _RETIRE_EPS)
            nb_f = jnp.where(fin, cnb, 0)
            used = used - jnp.sum(
                dem[ckn] * nb_f.astype(f32)[:, :, None], axis=1)
            nres = nres - nb_f.sum(axis=1)
            cnb = jnp.where(fin, 0, cnb)
            return (t, head, bleft, rr, used, nres, ckn, cnb, cfr, cta,
                    cin, cmb)

        return jax.lax.cond((nres_tot == 0) & (head < n), oversized,
                            lambda s: jax.lax.cond(nres_tot > 0,
                                                   complete,
                                                   lambda x: x, s), s)

    def not_done(s):
        t, head, bleft, rr, used, nres = s[:6]
        return (head < n) | (nres.sum() > 0)

    s0 = (f32(0.0), jnp.int32(0), nbk[row[0]], jnp.int32(0),
          jnp.zeros((U, D), f32), jnp.zeros((U,), jnp.int32),
          jnp.full((U, C), -1, jnp.int32), jnp.zeros((U, C), jnp.int32),
          jnp.zeros((U, C), f32), jnp.full((U, C), -1.0, f32),
          jnp.zeros((U, C), f32), jnp.zeros((U, C), f32))
    out = jax.lax.while_loop(not_done, step, s0)
    return out[0]


def _pack_f32(table):
    """Kernel-table arrays for the scan, cached on the ProfileTable."""
    cached = getattr(table, "_event_scan_pack", None)
    if cached is not None:
        return cached
    dev = table.device
    dims = tuple(dev.caps)
    dem = np.stack([
        np.array([k.demands.get(d, 0.0) for d in dims], dtype=np.float32)
        for k in table.kernels])
    pack = (
        np.array([int(k.n_blocks) for k in table.kernels], dtype=np.int32),
        dem,
        np.array([k.inst_per_block for k in table.kernels],
                 dtype=np.float32),
        np.array([k.mem_per_block() for k in table.kernels],
                 dtype=np.float32),
    )
    table._event_scan_pack = pack
    return pack


def event_times_jax(rows: np.ndarray, table) -> np.ndarray:
    """``jit(vmap)`` batch of :func:`event_scan_core` — rows (B, n)
    int indices into ``table.kernels``; returns (B,) float32 times."""
    if not HAS_JAX:
        raise RuntimeError("event_times_jax requires jax")
    nbk, dem, inst_b, mem_b = _pack_f32(table)
    cfg = config_for_device(table.device)
    fn = _jax_batch(cfg)
    return np.asarray(fn(jnp.asarray(rows, jnp.int32), jnp.asarray(nbk),
                         jnp.asarray(dem), jnp.asarray(inst_b),
                         jnp.asarray(mem_b),
                         jnp.asarray(cfg.caps, jnp.float32)))


@functools.lru_cache(maxsize=None)
def _jax_batch(cfg: EventScanConfig):
    core = functools.partial(event_scan_core, cfg=cfg)
    return jax.jit(jax.vmap(core,
                            in_axes=(0, None, None, None, None, None)))


def event_times_pallas(rows: np.ndarray, table, *,
                       interpret: bool | None = None) -> np.ndarray:
    """Pallas dispatch of the scan: ``grid=(B,)``, one order row per
    program, kernel table broadcast to all programs.  ``interpret``
    defaults to True unless a TPU is attached (tier-1 runs on CPU)."""
    if not HAS_JAX:
        raise RuntimeError("event_times_pallas requires jax")
    if interpret is None:
        interpret = jax.default_backend() not in ("tpu",)
    nbk, dem, inst_b, mem_b = _pack_f32(table)
    cfg = config_for_device(table.device)
    B, n = rows.shape
    K, D = dem.shape

    def kernel(row_ref, nbk_ref, dem_ref, inst_ref, mem_ref, caps_ref,
               out_ref):
        out_ref[0] = event_scan_core(
            row_ref[0, :], nbk_ref[...], dem_ref[...], inst_ref[...],
            mem_ref[...], caps_ref[...], cfg)

    call = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n), lambda b: (b, 0)),
            pl.BlockSpec((K,), lambda b: (0,)),
            pl.BlockSpec((K, D), lambda b: (0, 0)),
            pl.BlockSpec((K,), lambda b: (0,)),
            pl.BlockSpec((K,), lambda b: (0,)),
            pl.BlockSpec((D,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((1,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.float32),
        interpret=interpret,
    )
    return np.asarray(call(jnp.asarray(rows, jnp.int32),
                           jnp.asarray(nbk), jnp.asarray(dem),
                           jnp.asarray(inst_b), jnp.asarray(mem_b),
                           jnp.asarray(cfg.caps, jnp.float32)))


def event_times_reference(rows: np.ndarray, table) -> np.ndarray:
    """float64 oracle: ``_FastEventSim`` on each row (for tests)."""
    from repro.core.refine import _FastEventSim

    sim = _FastEventSim(table.device)
    out = np.empty(rows.shape[0], dtype=np.float64)
    for b in range(rows.shape[0]):
        order = [table.kernels[i] for i in rows[b]]
        out[b] = sim.simulate(order)[0]
    return out

"""Profiling hooks: phase names and breakdown views.

The engine and composer time their work with
:meth:`repro.obs.metrics.MetricsRegistry.timer` (or a histogram's
:meth:`~repro.obs.metrics.Histogram.time`, resolved once on hot paths)
under the ``phase_<name>`` histogram names listed in :data:`PHASES`.
Each timer is also a ``jax.profiler.TraceAnnotation`` span of the same
name, so a profiler trace shows every phase on the device planes'
clock, nested as below:

* ``phase_compose`` — everything between "step has a live mix" and
  "rounds are composed" (cache lookups, greedy, guard, refine, warm
  adaptation; recorded by ``ServingEngine.step``);
* ``phase_guard``   — gated/flat guard admission decisions inside the
  composer (a sub-interval of compose);
* ``phase_refine``  — refinement passes inside the composer (also a
  sub-interval of compose, so guard+refine <= compose);
* ``phase_execute`` — running the composed rounds (prefill/decode
  execution; recorded by ``ServingEngine.step``);
* ``phase_prefill`` — one request's replayed prompt, its first token's
  read-back included (inside execute; span metadata ``rid``,
  ``prompt_len``);
* ``phase_decode``  — one decode call: token upload, dispatch and
  read-back (inside execute; metadata ``rid``, ``pos``);
* ``phase_sync``    — the host blocked on the device for a chosen
  token's argmax (inside prefill or decode; metadata ``rid``);
* ``phase_audit``   — online quality audits
  (:class:`repro.obs.audit.QualityAuditor`) on the sampled steps —
  kept outside ``phase_compose`` so audit cost never pollutes the
  compose-time series the churn benchmarks guard.

Span metadata is never a registry label: the histograms stay one
series per phase.  The engine also counts ``decode_calls{kind=prefill}``
/ ``{kind=decode}`` (one per jitted decode-step call),
``tokens_emitted`` and, for a mixture-of-experts model,
``moe_dispatch{path=gathered|grouped}`` (its MoE layers per call,
under the local path a one-token call takes).

:func:`phase_breakdown` turns a registry into the per-step view
``benchmarks/serving.py`` prints.  Refiners report their own scoring
work under ``refine_evals`` / ``refine_score_s`` when handed a
``metrics=`` registry.
"""

from __future__ import annotations

from .metrics import Histogram, MetricsRegistry

__all__ = ["PHASES", "phase_breakdown"]

#: engine-step phases, in pipeline order; guard and refine are
#: sub-intervals of compose, prefill and decode of execute, sync of
#: prefill or decode; audit runs on sampled steps only
PHASES = ("compose", "guard", "refine", "execute", "prefill", "decode",
          "sync", "audit")


def phase_breakdown(metrics: MetricsRegistry) -> dict:
    """``{phase: {"calls", "total_s", "mean_s"}}`` for every phase in
    :data:`PHASES` (zeros for phases never entered, so the shape is
    stable across policies)."""
    out = {}
    for ph in PHASES:
        h = metrics.histogram(f"phase_{ph}")
        assert isinstance(h, Histogram)
        out[ph] = {"calls": h.count, "total_s": h.total,
                   "mean_s": h.mean}
    return out

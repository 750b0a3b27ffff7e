"""Self time per unit of work of the program's own spans
(``repro.common.spans``, recorded while the profiler runs) inside the
benchmark's ``window`` span; both are on ``time.perf_counter``. The unit
is a count the driver gives: ``grid_runs`` for a sweep, ``windows`` (the
admission windows) for the serving plane.

A span's self time is its duration minus the part its children cover;
children named in ``own`` count as its own. A span that crosses the
window's edge counts only its part inside. Gives ``None`` where the
program records no such span, where the driver gives no such count,
and where the program has no ``repro.common.spans`` at all."""


def self_ms_per(ctx, name, per, own=()):
    try:
        from repro.common import spans
    except ImportError:
        return None
    windows = [(t0, t1) for n, t0, t1 in ctx["recorder"].spans
               if n == "window"]
    if not windows or not ctx.get(per):
        return None
    lo, hi = windows[0][0], windows[-1][1]
    recs = [r for r in spans.records()
            if isinstance(r, spans.Span) and r.t1 > lo and r.t0 < hi]
    mine = {r.span_id for r in recs if r.name == name}
    if not mine:
        return None

    def inside(r):
        return min(r.t1, hi) - max(r.t0, lo)

    sec = sum(inside(r) for r in recs if r.span_id in mine)
    sec -= sum(inside(r) for r in recs
               if r.parent_id in mine and r.name not in own)
    return 1e3 * sec / ctx[per]


"""Estimator scatter and belief fold (``serving/gateway.py``
``observe_detections_window`` -> ``_obs_counts``, ``observe_window`` ->
``_observe_win``): host time of the two calls per admission window,
programs they compile inside the window included, from the benchmark's
spans around them."""


def read(ctx):
    names = ctx.get("observe_spans")
    if not names or not ctx.get("windows"):
        return None
    return 1e3 * ctx["recorder"].span_seconds(names) / ctx["windows"]

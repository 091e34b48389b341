"""Per ``run_pipeline`` call, the host time of the call: the planning
of its steps and their dispatch, the captured scan's included.  The
window keeps calls dispatched ahead, so the chip runs earlier calls
meanwhile; this moves ``step_ms`` once it nears a call's device time.
Mean over the traced calls, from the benchmark's spans."""


def read(run, cell, peaks):
    calls = run.spans.named("call")
    if not calls:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in calls) / len(calls)

"""Device-idle time per training step while the host was in one of
``Run.step``'s own spans (``repro.step.put``, ``.dispatch``, ``.fetch``):
each idle gap goes to the host span that overlaps most of it."""
import scopes

UNIT = "ms"


def read(ctx):
    sc = scopes.Scoped.of(ctx)
    if not (sc.step_spans() and sc.trace.devices) or ctx["steps"] < 1:
        return None
    idle = sum(v for name, v in sc.idle_by_span().items()
               if name.startswith(scopes.STEP_SPANS))
    return 1e3 * idle / ctx["steps"]

"""Host time of one step outside the executable call: the sum of the
medians of the step engine's phases other than ``step/dispatch``
(prepare, lookup, place, install, bookkeeping) and of ``optimizer/step``,
from the program's ``smp_host_phase_seconds`` histogram. Under
``fused_step_donation`` the call is synchronous, so the chip idles for
about this long between steps."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    p50 = _scopes.host_phase_p50_s()
    if not any(phase in p50 for phase in _scopes.HOST_PHASES):
        return None
    return 1e3 * sum(p50.get(phase, 0.0) for phase in _scopes.HOST_PHASES)

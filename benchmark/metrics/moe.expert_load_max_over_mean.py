"""Largest held expert's load over the mean load, averaged over the
expert layers, over the window's steps: the program's
``smp_moe_expert_load_max_over_mean{layer}`` gauges as the driver's last
``record_moe_stats`` call set them. 1.0 is perfect balance."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    values = [s["value"] for s in
              _scopes._series("smp_moe_expert_load_max_over_mean")]
    return sum(values) / len(values) if values else None

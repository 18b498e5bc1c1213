"""Counts the program and jax keep, read before and after a phase.

The logic is ``chip_smoke.py``'s (``XlaCounts``, ``counters``,
``delta``), copied so that the yardstick does not move with it."""


class XlaCounts:
    """jax's own compile events: requests that consulted the persistent
    cache, hits read back from it, compiles the backend really ran."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs


def snapshot(xla: XlaCounts) -> dict:
    from spark_rapids_tpu.runtime import resilience
    from spark_rapids_tpu.runtime import telemetry as TM
    from spark_rapids_tpu.runtime.kernel_cache import compile_snapshot
    kernel_compiles, kernel_compile_s = compile_snapshot()
    res = resilience.counters_snapshot()
    return {
        "kernel_compiles": kernel_compiles,
        "kernel_compile_s": kernel_compile_s,
        "xla_compile_requests": xla.requests,
        "persistent_cache_hits": xla.hits,
        "xla_compiles": xla.compiles, "xla_compile_s": xla.compile_s,
        # the laddered kernels only (hash layout, match, sort rungs),
        # not every program launched: which rung ran, not how many
        "dispatch_by_backend": TM.REGISTRY.labeled_counter(
            "tpuq_kernel_dispatch_total", label="backend").child_values(),
        "ladder_descents": TM.REGISTRY.labeled_counter(
            "tpuq_kernel_fallback_total", label="kernel").child_values(),
        "host_degraded_ops": res["host_degraded_ops"],
        "breaker_trips": res["breaker_trips"],
    }


def delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            d = {lk: lv - before[k].get(lk, 0) for lk, lv in v.items()}
            out[k] = {lk: int(lv) for lk, lv in d.items() if lv}
        else:
            out[k] = v - before[k]
    return out

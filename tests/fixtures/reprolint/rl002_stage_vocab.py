"""Seeded RL002 violations: stage strings outside the STAGES vocabulary."""


def instrument(tracer, tr, registry, dt):
    with tracer.span("warp_speed"):  # seeded: RL002 (not a stage)
        pass
    tr.add("decoed", dt)  # seeded: RL002 (typo'd stage)
    registry.observe("stage_latency_seconds", dt, stage="telemetry")  # seeded: RL002
    with tracer.span("plan_build"):  # allowed: in STAGES
        pass
    tr.add("encode", dt)  # allowed: in STAGES
    with tracer.annotate("evaluate"):  # seeded: RL002 (annotation of no stage)
        pass
    tr.open_span("decode_wait")  # seeded: RL002
    with tracer.annotate("executor_wait"):  # allowed: in STAGES
        pass

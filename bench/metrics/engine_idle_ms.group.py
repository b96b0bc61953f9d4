"""Device idle time inside the engine's own spans, per subject visit (ms).

Sums the idle gaps whose host part (after ``" / "``) is a ``repro.<stage>``
profiler annotation. A gap counts only where no deeper runtime event is
open inside the program span: a gap under ``DeferredTpuAllocator::Allocate``
or a device-to-host copy keeps that event's label and is not counted here.
None where the trace has no such gap, as in a program without the spans.
"""


def read(rec):
    dt, visits = rec.get("device_trace"), rec.get("visits")
    if not dt or not visits:
        return None
    gaps = [s for label, s in dt["idle_gaps"] if label.partition(" / ")[2].startswith("repro.")]
    return 1e3 * sum(gaps) / visits if gaps else None

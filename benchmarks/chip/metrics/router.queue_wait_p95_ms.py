"""Front door and router: 95th percentile over the requests due in the
window of the host-clock wait from the due time to the first prefill wave
of the batch that holds the request (the wave's span opens when the
prefill member takes the batch off its queue).  Moves ttft_p95_ms."""
from benchmarks.chip.metrics import percentile


def read(run):
    waits = [(r.first_wave - r.due) * 1e3 for r in run.window_records()
             if r.first_wave is not None]
    return percentile(waits, 95)

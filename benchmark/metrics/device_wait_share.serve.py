"""Of all the time the engine's rounds took on the host, the share spent
blocked on a finished horizon's block: the device is what the loop waits
for. The rest is host work the device does not hide. It mixes the device's
speed with the host's cost: read its direction only between two runs whose
device tick (`decode_tick_ms_p50.serve`) is the same. A shorter tick lowers
it with no change on the host, and that is the host coming out from behind
the device, which `sched_round_ms_p50.serve` then prices."""
from benchmark.records import PHASES, horizons


def read(run):
    events = horizons(run)
    total = sum(ev[p] for ev in events for p in PHASES)
    if total <= 0.0:
        return None
    return 100.0 * sum(ev["fetch_wait_s"] for ev in events) / total

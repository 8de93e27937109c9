"""Host part of ``genomes.finish`` per generation: the span's time after
the device finished the generation's program (report arrays with the
union-find connectivity pass, copies of the results)."""
import spans


def read(rec):
    if "trace" not in rec or not rec.get("spans"):
        return None
    waits = spans.device_waits(rec, "opt.device_wait")
    fins = spans.in_window(rec, "genomes.finish")
    if not waits or not fins:
        return None
    host = 0.0
    for s, e in fins:
        done = next((d for ws, d, we in waits if ws <= s and e <= we), s)
        host += e - max(s, done)
    return host / len(waits) / 1e6

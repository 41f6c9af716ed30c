"""Required forward operations of the real prompt tokens answered
(``bench/flops``, head at one position per prompt) over the device's busy
time in the traced window x bf16 peak, in %."""


def read(rec):
    t, c = rec.trace, rec.counters
    if t is None or t.busy_s <= 0 or not c.get("serve_required_flops"):
        return None
    return 100.0 * c["serve_required_flops"] / (t.busy_s * rec.peak["bf16_flops"])

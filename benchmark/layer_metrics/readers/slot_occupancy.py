"""busy_slot_steps / (decode_steps x num_slots), from the frontend's
counters over the traced window."""


def read(reading):
    counters = reading.get("counters")
    if not counters or not counters.get("decode_steps"):
        return None
    return 100.0 * counters["busy_slot_steps"] / (
        counters["decode_steps"] * reading["num_slots"])

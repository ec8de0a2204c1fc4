"""Share of the exchange's roofline: the least time of the bytes each
chip sends (``bench/exchange_work.py``: (n - 1) / n of its share of the
Delta block, 8 bytes per m, ring and map) over the published
chip-to-chip bandwidth (``ici_bits_per_s`` in ``bench/peaks.json``), over
the exchange's device seconds per call (``exchange_s``)."""

import dist_trace
import program_trace as pt
from exchange_work import exchange_work


def read(record):
    per = dist_trace.seconds_per_call(record, __file__)
    t, peaks = record.get("trace"), record.get("peaks")
    s = per.get(pt.names().EXCHANGE) if per else None
    if not s or not t or not t["n_devices"] or not peaks:
        return None
    c = record["config"]
    w = exchange_work(c["m_max"], c["n_rings"], int(record["traffic"]["K"]),
                      t["n_devices"], c.get("spin", 0))
    return 100.0 * w["bytes_per_chip"] / (peaks["ici_bits_per_s"] / 8) / s

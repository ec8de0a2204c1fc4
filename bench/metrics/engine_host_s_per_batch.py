"""Median over the window's batches of the engine's host seconds per
batch: its ``engine.form``, ``engine.stack``, ``engine.upload``,
``engine.download`` and ``engine.scatter`` spans, joined by the batch
number they carry (``repro.tracing.ENGINE_HOST``)."""

import program_trace as pt


def read(record):
    return pt.engine_host_s_per_batch(pt.of_reader(__file__))

"""The readers of the metrics that the program's own spans and the split
programs feed: nothing where a layer lacks their input (a program without
the spans, or a run that traced nothing), and the value on a layer built by
hand."""
import pytest

import run

SPANS = {"engine_queue": {"seconds": 0.5, "count": 100, "max_s": 0.03},
         "engine_batch": {"seconds": 2.3, "count": 90, "max_s": 0.0312}}
TRACE = {"program_calls": {"_insert_wave": 241, "split_node": 474}}
LAYER = {"spans": SPANS, "trace": TRACE, "batches_traced": 79}

READERS = {
    "engine.queue_wait_ms": 5.0,                 # 0.5 s over 100 requests
    "engine.batch_max_ms": 31.2,
    "build.split_rounds_per_batch": 6.0,         # 474 split programs over 79 batches
}

# layers without a reader's input: no readings at all; a program without the
# spans, or totals with no max_s; nothing counted (no request, no batch)
LACKING = [
    {},
    {"spans": {}, "batches_traced": 79},
    {"spans": {"engine_batch": {"seconds": 2.3, "count": 90}}, "trace": TRACE},
    {"spans": {"engine_queue": {"seconds": 0.0, "count": 0, "max_s": 0.0}},
     "trace": TRACE, "batches_traced": 0},
]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value_on_a_layer(name):
    assert run.read_metric(name, LAYER) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_without_its_input(name):
    assert all(run.read_metric(name, layer) is None for layer in LACKING), name

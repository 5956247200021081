"""The reader of ``train.feed_slot_reuse_pct`` (PR 27): on hand-built span
totals, on a program without the feed's slots (the parent of that PR: the
reader returns None and the line leaves the metric out), and through a
rehearsal of the cell at toy sizes on the CPU."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = "train.feed_slot_reuse_pct"
CELL = "inception_v1.train_b256"
TOY = {"batch": 8, "records": 32, "classes": 10, "reference_block": 4,
       "check": {"loss_gap": 1e-4, "first_grad_gap": 0.15,
                 "change3_gap": 0.15, "change3_error": 0.11}}


def read(obs):
    return harness.load_reader(NAME)(obs)


def test_manifest_entry_is_the_last_and_names_the_cell():
    entry = harness.load_manifest()["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "dataset",
                     "moves": "train_records_per_s", "workloads": [CELL]}


def test_share_of_the_draws_and_the_wait_per_batch(capsys):
    spans = {"data-load/fetch": (0.40, 20), "feed/slot-wait": (0.70, 19)}
    assert read({"spans": spans, "steps": 20}) == pytest.approx(95.0)
    assert "feed slot wait: 35.000 ms/batch" in capsys.readouterr().err
    spans["feed/slot-wait"] = (0.0, 0)          # every batch fell back
    assert read({"spans": spans}) == 0.0


@pytest.mark.parametrize("spans", [
    None, {},
    {"data-load/fetch": (1.8, 10), "h2d/prefetch": (0.38, 10)},  # parent
    {"feed/slot-wait": (0.1, 0)},                       # no draw booked
])
def test_none_where_the_program_has_no_such_path(spans):
    assert read({} if spans is None else {"spans": spans}) is None


def test_rehearsal_recycles_every_batch_of_the_window():
    r = harness.run_cell(CELL, 27, 1.0, True, sizes=TOY)
    assert r["correct"] is True, r["check"]
    assert r["metrics"][NAME] == {"value": 100.0, "unit": "%"}

"""``union_reloads_per_step``: the window difference of the program's
``union_reloads`` per decode step, declared for the chat cell, and None,
without raising, for a program that does not report the counter or a
window without decode steps."""

import json

import pytest

import cell

NAME = "union_reloads_per_step"


def ctx_for(stats_open, stats_close, steps):
    calls = ([{"kind": "step", "in_win": True}] * steps
             + [{"kind": "step", "in_win": False}])
    return cell.Ctx(stats_open=stats_open, stats_close=stats_close,
                    calls=calls)


def test_declared_for_the_chat_cell():
    bench = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    metric = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert metric["workloads"] == ["phi-moe.chat"]
    assert metric["moves"] == "decode_tok_s"


@pytest.mark.parametrize("opened,closed,steps,want", [
    (10, 22, 4, 3.0), (7, 7, 5, 0.0)])
def test_reads_the_reloads_per_window_step(opened, closed, steps, want):
    read = cell.load_reader(NAME)
    ctx = ctx_for({"union_reloads": opened, "loads_hi": 1},
                  {"union_reloads": closed, "loads_hi": 9}, steps)
    assert read(ctx) == want


def test_reads_none_without_the_counter_or_steps():
    read = cell.load_reader(NAME)
    assert read(ctx_for({"loads_hi": 1}, {"loads_hi": 2}, 3)) is None
    assert read(ctx_for({"union_reloads": 1}, {"loads_hi": 2}, 3)) is None
    assert read(ctx_for({"union_reloads": 1}, {"union_reloads": 4}, 0)) is None

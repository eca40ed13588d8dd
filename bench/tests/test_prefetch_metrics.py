"""``prefetch_dropped_per_step`` and ``prefetch_unused_per_step``: the
window differences of the program's ``dropped_prefetch`` and
``prefetch_unused`` per decode step, declared for the chat cell in the
staging layer, and None, without raising, for a program that does not
report the counter or a window without decode steps."""

import json

import pytest

import cell

METRICS = {"prefetch_dropped_per_step": "dropped_prefetch",
           "prefetch_unused_per_step": "prefetch_unused"}


def ctx_for(stats_open, stats_close, steps):
    calls = ([{"kind": "step", "in_win": True}] * steps
             + [{"kind": "step", "in_win": False}])
    return cell.Ctx(stats_open=stats_open, stats_close=stats_close,
                    calls=calls)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_declared_for_the_chat_cell(name):
    bench = json.loads((cell.ROOT / "BENCHMARK.json").read_text())
    metric = {m["name"]: m for m in bench["per_layer"]}[name]
    assert metric["workloads"] == ["phi-moe.chat"]
    assert metric["moves"] == "decode_tok_s"
    assert metric["layer"] == "staging"


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("opened,closed,steps,want", [
    (10, 22, 4, 3.0), (7, 7, 5, 0.0)])
def test_reads_the_counter_per_window_step(name, opened, closed, steps, want):
    read = cell.load_reader(name)
    counter = METRICS[name]
    ctx = ctx_for({counter: opened, "loads_hi": 1},
                  {counter: closed, "loads_hi": 9}, steps)
    assert read(ctx) == want


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reads_none_without_the_counter_or_steps(name):
    read = cell.load_reader(name)
    counter = METRICS[name]
    assert read(ctx_for({"loads_hi": 1}, {"loads_hi": 2}, 3)) is None
    assert read(ctx_for({counter: 1}, {"loads_hi": 2}, 3)) is None
    assert read(ctx_for({counter: 1}, {counter: 4}, 0)) is None

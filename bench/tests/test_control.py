"""The control at a size a test run holds: the reference in float8 e4m3,
put in the program's place, goes through the cell's own comparison
(bench/correct.py with the cell's limits) and comes out not correct, while
the program on the same served tokens comes out correct. At the cell's
own size PERF.md gives the readings the limit was set from."""
from bench import correct
from bench.control import readings
from bench.run import prepare
from bench.tests.small import cell, cpu_only, fixed_pool


def test_control_fails_the_cells_comparison(monkeypatch):
    fixed_pool(monkeypatch, blocks=128)
    bench, wl, conf, mix = cell()
    # wide and deep enough that e4m3's rounding moves the top token
    conf.update(hidden_size=128, intermediate_size=256, num_hidden_layers=4,
                vocab_size=1024)
    seed = 2**31 + 202
    prep = prepare(conf, mix, seed, cpu_only(), out=lambda *a: None)
    r = readings(prep, conf, mix, correct.limits(wl["name"]), seed, 2.0)
    assert r["requests"] >= 1
    assert r["program_correct"] is True, r
    assert r["control_correct"] is False, r

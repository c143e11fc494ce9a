"""The knee sweep's rule, the open loop's window counts, and the prefill
widths a plan warms."""
import bench_tiny as bt


def _line(seed, rate, offered, served):
    return {"seed": seed, "rate": rate, "offered_tok_s": offered,
            "served_tok_s": served}


def test_knee_is_the_last_rate_that_still_raised_served_tokens():
    from bench import sweep

    rates = [1.0, 2.0, 3.0, 4.0]
    lines = []
    for seed in (1, 2):
        lines += [_line(seed, 1.0, 100, 95), _line(seed, 2.0, 200, 190),
                  _line(seed, 3.0, 300, 260), _line(seed, 4.0, 400, 280)]
    assert sweep.knee(lines, rates) == 3.0
    # one seed that stops gaining at 3 moves the knee for both
    lines[6] = _line(2, 3.0, 300, 230)
    assert sweep.knee(lines, rates) == 2.0
    # never past it within the sweep
    gaining = [_line(1, r, 100 * r, 95 * r) for r in rates]
    assert sweep.knee(gaining, rates) == 4.0


def test_saturated_capacity_stands_in_below_the_lowest_rate():
    from bench import sweep

    rates = [1.0, 2.0, 3.0]
    lines = [_line(1, 1.0, 200, 110), _line(1, 2.0, 400, 120),
             _line(1, 3.0, 600, 100)]
    assert sweep.knee(lines, rates) is None
    # 110 served tokens/s over 200-token replies
    assert sweep.capacity(lines, None) == 110 / 200
    assert sweep.capacity(lines, 1.0) == 110 / 200  # median of 120 and 100


def test_open_loop_counts_what_waits_at_the_close():
    from bench.drivers import open_loop

    t = bt.chat_traffic()
    t.update(rate_per_s=200.0, output=dict(t["output"], median=16, min=16))
    d = open_loop.Driver(bt.olmo_cfg(), t, 2 ** 31 + 21)
    d.seconds, d.drain = 0.3, False
    d.setup()
    d.window(0.3)
    c = d.counts()
    assert c["due"] == len(d.offered) == round(200.0 * 0.3)
    assert c["offered_tokens"] == sum(r.max_new for r in d.offered)
    assert c["waiting_at_close"] == c["due"] - len(d.done) > 0
    assert 0 < c["served_tokens"] <= sum(len(r.out) for r in d.done)
    assert all(len(r.out) == r.max_new for r in d.done)


def test_planned_widths_give_one_length_per_bucket():
    from bench import serving

    assert serving.planned_widths([5, 8, 9, 16, 17, 300, 290]) == [8, 16, 17, 290]

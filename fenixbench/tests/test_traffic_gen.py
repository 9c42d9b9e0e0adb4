"""The traffic generator: stationary concurrency, a stream whose trace
time never runs backwards, and a capture the program reads back as the
same columns."""

import numpy as np

from fenixbench import traffic_gen as tg

SEED = 2**31 + 99


def small_mix():
    mix = tg.load_mix("iscx_steady")
    mix.update(concurrent_flows=2048, pool_trace_us=300000,
               segment_pkts=4096, base_flows=2048)
    return mix


def test_timestamps_never_decrease_across_pool_cycles():
    mix = small_mix()
    pool = tg.make_pool(mix, SEED)
    per = tg.n_segments(pool, mix["segment_pkts"])
    assert per >= 2
    ts = np.concatenate([tg.cycle_segment(pool, k, mix["segment_pkts"])
                         ["ts_us"] for k in range(3 * per + 1)])
    assert (np.diff(ts.astype(np.int64)) >= 0).all()
    assert ts[0] >= 1


def test_concurrency_holds_the_mix_figure():
    mix = small_mix()
    pool = tg.make_pool(mix, SEED)
    T = pool["period_us"]
    alive = tg.concurrency(pool, np.linspace(0.1 * T, 0.9 * T, 17)
                           .astype(np.int64))
    target = mix["concurrent_flows"]
    assert np.abs(alive - target).max() <= 0.1 * target


def test_same_seed_same_pool_and_large_seed():
    mix = small_mix()
    a, b = tg.make_pool(mix, SEED), tg.make_pool(mix, SEED)
    for k in tg.PKT_COLS:
        np.testing.assert_array_equal(a[k], b[k])
    c = tg.make_pool(mix, SEED + 1)
    assert not np.array_equal(a["ts_us"][:100], c["ts_us"][:100])


def test_capture_reads_back_as_the_columns(tmp_path):
    from repro.data.trace_ingest import iter_pcap_packets

    mix = small_mix()
    pool = tg.make_pool(mix, SEED)
    n = 3000
    cols = {k: pool[k][:n] for k in tg.PKT_COLS}
    path = str(tmp_path / "cap.pcap")
    assert tg.write_pcap(cols, path) == n
    got = list(iter_pcap_packets(path, chunk_pkts=1024))
    for k in tg.PKT_COLS:
        np.testing.assert_array_equal(
            np.concatenate([c[k] for c in got]), cols[k], err_msg=k)
    assert set(np.unique(cols["proto"])) == {6, 17}

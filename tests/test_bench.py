"""bench.py's transformer row at a tiny config (its no-TPU exit is
pinned in `test_chip_smoke.py`, next to chip_smoke.py's own).
"""


def test_run_transformer_tiny_cpu():
    """The second-flagship transformer bench path runs end to end at a
    tiny config: finite tokens/s, and the budget re-check logic
    doesn't trip at full budget."""
    import bench

    tps, mfu, _pallas = bench.run_transformer(
        iters=1, warmup=1, B=2, T=64, d_model=32, n_layers=2,
        d_ff=64, vocab=128)
    assert tps > 0
    assert mfu >= 0

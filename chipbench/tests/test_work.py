"""Required work and the peaks table."""
import pytest

from harness import env, work


def test_train_alias_query_epoch_bytes_match_the_hand_worked_figure():
    spec = env.load_spec("train-alias-query")
    cfg = spec.config
    _, nbytes = work.alias_epoch_work(
        cfg["corpus_tokens"], cfg["corpus_queries"], cfg["n_topics"],
        cfg["vocab_rows_trained"], cfg["n_mh"])
    # 1,016,555 tokens × 232 B of probes + 821 × 10⁵ × 16 B of rebuild
    assert nbytes == pytest.approx(1.55e9, rel=0.01)
    least, bound = work.least_time(20.0 * 4 * 1016555, nbytes, "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(1.89e-3, rel=0.02)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")

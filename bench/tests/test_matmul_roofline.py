"""``kernels.matmul_roofline`` counts only the work of the operations whose
time it divides by.

Checked on made-up reductions, worked by hand: the whole count where no
kernel scope holds time (the CPU twin, or a program without the kernels),
attention's term left out where ``kernels.flash`` holds time on any device;
and a family that is new files only (a toy module in a temporary checkout)
whose ``KERNEL_SCOPES`` maps a second term to a second scope, read by the
matmul and flash readers as they stand."""

import os
import sys
import types

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)

from harness import scopes, spec  # noqa: E402

NAME = "kernels.matmul_roofline"
PEAK = 197e12

#: a dense decoder small enough to count by hand
TOY = {"family": "dense_decoder", "hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "intermediate_size": 16, "head_dim": 4,
       "num_hidden_layers": 3, "vocab_size": 10}
#: 6 x (3 layers x (8*(2+2)*4 + 2*4*8 + 3*8*16) + 8*10) per token
MATMUL = 6.0 * (3 * (128 + 64 + 384) + 80)
#: 3 x 3 layers x 2*2 x 2 heads x 4 x S/2, at S=6
ATTENTION = 3.0 * 3 * 4 * 2 * 4 * 3


def _record(config, matmul_s=(0.25, 0.15), steps=3, batch=2, seq=6):
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=config),
        trace=[types.SimpleNamespace(matmul_s=t) for t in matmul_s],
        steps_traced=steps, tokens_traced=steps * batch * seq, seq=seq,
        peaks={"bf16_flops_per_s": PEAK})


def _share(per_token, rec):
    return 100.0 * per_token * rec.tokens_traced / (
        PEAK * sum(d.matmul_s for d in rec.trace))


def test_silent_without_matmul_time_or_traced_steps(monkeypatch):
    read = spec.metric_reader(NAME)
    monkeypatch.setattr(scopes, "seconds", lambda: [])
    assert read(_record(TOY, matmul_s=())) is None
    assert read(_record(TOY, matmul_s=(0.0, 0.0))) is None
    assert read(_record(TOY, steps=0)) is None


@pytest.mark.parametrize("per_device", [
    [], [{}, {}], [{"model.attention": 0.3, "model.blocks": 2.0}],
    [{"kernels.flash": 0.0}]], ids=["no-scopes", "empty", "xla-attention",
                                    "flash-scope-idle"])
def test_whole_count_where_no_kernel_scope_holds_time(per_device,
                                                      monkeypatch):
    monkeypatch.setattr(scopes, "seconds", lambda: per_device)
    rec = _record(TOY)
    assert spec.metric_reader(NAME)(rec) == pytest.approx(
        _share(MATMUL + ATTENTION, rec), rel=1e-12)


@pytest.mark.parametrize("per_device", [
    [{"kernels.flash": 0.2, "model.attention": 0.21}, {"kernels.flash": 0.1}],
    [{}, {"kernels.flash": 1e-6}]], ids=["every-device", "one-device"])
def test_attention_left_out_where_the_flash_scope_holds_time(per_device,
                                                             monkeypatch):
    monkeypatch.setattr(scopes, "seconds", lambda: per_device)
    rec = _record(TOY)
    # 3 steps x 2 rows x 6 tokens = 36 tokens; 0.40 s of matmul time
    assert rec.tokens_traced == 36
    assert spec.metric_reader(NAME)(rec) == pytest.approx(
        100.0 * MATMUL * 36 / (PEAK * 0.40), rel=1e-12)


# --- a family that is new files only ---------------------------------------

TOY_MOE = '''
KERNEL_SCOPES = {"attention": "kernels.flash", "experts": "kernels.gmm"}


def terms(c, seq_len):
    return {"matmul": 600.0, "attention": 30.0 * seq_len, "experts": 70.0}


def flops_per_token(c, seq_len):
    return sum(terms(c, seq_len).values())
'''


@pytest.fixture
def moe_root(tmp_path):
    """A checkout holding the two roofline readers as they stand and a toy
    family ``toy_moe`` that the real checkout does not have."""
    for d in ("metrics", "flops"):
        os.makedirs(tmp_path / "bench" / d)
    for name in (NAME, "kernels.flash_roofline"):
        os.symlink(os.path.join(BENCH, "metrics", name + ".py"),
                   tmp_path / "bench" / "metrics" / (name + ".py"))
    (tmp_path / "bench" / "flops" / "toy_moe.py").write_text(TOY_MOE)
    return str(tmp_path)


@pytest.mark.parametrize("ran, per_token", [
    ((), 600.0 + 30.0 * 6 + 70.0),
    (("kernels.flash",), 600.0 + 70.0),
    (("kernels.gmm",), 600.0 + 30.0 * 6),
    (("kernels.flash", "kernels.gmm"), 600.0),
])
def test_a_new_familys_kernel_terms_leave_the_matmul_work(
        moe_root, ran, per_token, monkeypatch):
    monkeypatch.setattr(scopes, "seconds", lambda: [
        dict({"model.blocks": 1.0}, **{s: 0.05 for s in ran})])
    rec = _record({"family": "toy_moe"})
    assert spec.metric_reader(NAME, moe_root)(rec) == pytest.approx(
        _share(per_token, rec), rel=1e-12)


def test_a_new_familys_flash_roofline_counts_its_mapped_term(moe_root,
                                                             monkeypatch):
    monkeypatch.setattr(scopes, "seconds", lambda: [
        {"kernels.flash": 0.25, "kernels.gmm": 0.5}, {"kernels.flash": 0.15}])
    rec = _record({"family": "toy_moe"})
    assert spec.metric_reader("kernels.flash_roofline", moe_root)(
        rec) == pytest.approx(100.0 * 30.0 * 6 * 36 / (PEAK * 0.40),
                              rel=1e-12)

"""chip_smoke.py at a tiny size on the CPU: the same phases and checks
the chip run makes (Runner ingest, synchronous warm, from-cache sweeps,
HTTP admission bursts, verdicts against RegoDriver, zero ladder rungs),
so a later change that breaks the smoke fails here before it costs a
chip call."""

import chip_smoke as smoke

TINY = dict(namespaces=10, violating_share=0.02, bursts=2, burst_size=32,
            audit_sample=60, admission_sample=16)


def test_main_refuses_a_cpu(capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert not any(line.startswith("{") for line in out)


def test_one_chip_smoke_tiny(capsys):
    smoke.one_chip_smoke(smoke.Scale(pods=1500, constraints=40, **TINY), 0)
    out = capsys.readouterr().out
    assert "cold_batches: 0" in out
    assert "interpreter-routed templates: none" in out


def test_four_chip_audit_tiny(capsys):
    smoke.four_chip_audit(smoke.Scale(pods=600, constraints=24, **TINY), 0)
    out = capsys.readouterr().out
    for c_shards in (1, 2, 4):
        assert f"c_shards={c_shards}: identical to single-device" in out

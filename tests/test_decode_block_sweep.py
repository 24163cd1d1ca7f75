"""The decode-attention block sweep runs, and its checks pass, at tiny
shapes in interpret mode."""
import json

from benchmarks import decode_block_sweep


def test_small_sweep_checks_pass(capsys):
    assert decode_block_sweep.main(["--small"]) == 0
    row = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row["poisoned_equal"] and row["finite"] and row["empty_row_zero"]
    assert {"rule_ms", "ppb2_ms"} <= set(row)

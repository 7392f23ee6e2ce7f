"""The control, the bfloat16 reference put in the program's place, comes
out not correct on three seeds: its distances miss the float32 limit."""
import json

import control


def test_bfloat16_control_is_not_correct(tiny_root, capsys):
    argv = ["--workload", "tiny.mix", "--seconds", "1",
            "--seeds", "1", str(2 ** 31 + 5), "-9"]
    assert control.main(argv, root=tiny_root, platform="cpu") == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(lines) == 3
    for line in lines:
        assert line["correct"] is False
        c = line["checks"]["dist_err"]
        assert c["value"] > c["limit"]

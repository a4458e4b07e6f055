"""``tools/lowered_text.py diff``: the verdict it gives of two dumps
of lowered programs (the dumps themselves are jax's text)."""
import pytest

from tools import lowered_text

A = """module @jit_f {
  %0 = stablehlo.iota dim = 0 : tensor<4xi32>
  %1 = stablehlo.add %arg0, %0 : tensor<4xi32>
  %2 = stablehlo.multiply %1, %1 : tensor<4xi32>
}
"""
# the iota after the multiply's operand is made, values renumbered
REORDERED = """module @jit_f {
  %3 = stablehlo.add %arg0, %4 : tensor<4xi32>
  %4 = stablehlo.iota dim = 0 : tensor<4xi32>
  %5 = stablehlo.multiply %3, %3 : tensor<4xi32>
}
"""
WITHOUT_THE_ADD = """module @jit_f {
  %0 = stablehlo.iota dim = 0 : tensor<4xi32>
  %2 = stablehlo.multiply %arg0, %arg0 : tensor<4xi32>
}
"""


@pytest.mark.parametrize("other,rc,says", [
    (A, 0, "f.txt: identical"),
    (REORDERED, 0, "f.txt: the same operations in another order"),
    (WITHOUT_THE_ADD, 1, "1 operation(s) only in"),
], ids=["identical", "reordered", "an operation gone"])
def test_diff_verdict(tmp_path, capsys, other, rc, says):
    for name, text in (("parent", A), ("change", other)):
        (tmp_path / name).mkdir()
        (tmp_path / name / "f.txt").write_text(text)
    assert lowered_text.diff(str(tmp_path / "parent"),
                             str(tmp_path / "change")) == rc
    out = capsys.readouterr().out
    assert says in out
    if rc:
        assert "- x1 % = stablehlo.add %, % : tensor<4xi32>" in out

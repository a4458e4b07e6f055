"""``tools/lowered_text.py``: the verdict ``diff`` gives of two dumps
of lowered programs (the dumps themselves are jax's text), and the
tree's own dump against its record, ``tests/lowered_text.json``."""
import json
import os

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


# ------------------------------------------------- the tree's own programs
#
# ``tests/lowered_text.json`` is ``tools/lowered_text.py digest`` of the
# tree's dump. PR 31 (the span ladder of the paged decode read) wrote it:
# every program but the four paged decode steps had the text of that PR's
# parent, so a later change to a slab, block or train program shows here.
# PR 35 (the KDA chunked scan's chunk-local part hoisted out of its
# loop) wrote ``kimi_linear_prefill_block_bfloat16`` anew, the one
# recorded program that holds the scan; every other text stayed.
# A PR that means to change one writes the record anew:
#   JAX_PLATFORMS=cpu python tools/lowered_text.py dump . /tmp/lt
#   python tools/lowered_text.py digest /tmp/lt tests/lowered_text.json

RECORD = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "lowered_text.json")))
LAYERS = {"llama_gqa": 2, "llama_gqa_w8": 2, "xing4": 3, "kimi_linear": 1}


@pytest.fixture(scope="module")
def dumped(tmp_path_factory):
    """The tree's dump, made as the tool's own process makes it
    (conftest turns x64 on; the programs run without it)."""
    import jax

    out = tmp_path_factory.mktemp("lowered")
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        lowered_text.dump_programs(
            os.path.dirname(os.path.dirname(__file__)), str(out))
    finally:
        jax.config.update("jax_enable_x64", x64)
    return lowered_text.digest(str(out))


def test_dump_holds_the_recorded_programs(dumped):
    assert sorted(dumped) == sorted(RECORD)


@pytest.mark.parametrize("program", sorted(RECORD))
def test_program_text_is_the_recorded_one(dumped, program):
    """Slab, block and train programs: the text PR 31's parent had.
    A paged decode step: exactly one ``stablehlo.case`` a layer, the
    span ladder; no other program holds one."""
    tag, _, rest = program.partition("_decode_paged_")
    assert dumped[program]["cases"] == (LAYERS[tag] if rest else 0)
    assert dumped[program] == RECORD[program]

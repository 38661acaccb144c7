"""Are two directories of ``hack/lower_cells.py`` texts the same programs?

    JAX_PLATFORMS=cpu python hack/cmp_lowered.py out_a out_b

Outside the ``tpu_custom_call`` bodies the texts are compared byte for
byte. A serialized Mosaic body holds its callers' file names and line
numbers, so two bodies that differ are parsed (MLIR bytecode, base64) and
compared as printed WITHOUT debug locations. Exit code 1 if any program
differs in more than locations. The builder's check before a chip run."""
import base64
import difflib
import os
import re
import sys

from jax._src.interpreters import mlir as jmlir
from jax._src.lib.mlir import ir

BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def printed(b64: str) -> str:
    ctx = jmlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(b64))
        return module.operation.get_asm(enable_debug_info=False)


def main(a: str, b: str) -> int:
    bad = 0
    for name in sorted(os.listdir(a)):
        ta = open(os.path.join(a, name)).read()
        tb = open(os.path.join(b, name)).read()
        if ta == tb:
            print("byte-equal            ", name)
            continue
        bodies_a, bodies_b = BODY.findall(ta), BODY.findall(tb)
        if (BODY.sub("<body>", ta) != BODY.sub("<body>", tb)
                or len(bodies_a) != len(bodies_b)):
            print("DIFFERS OUTSIDE BODIES", name)
            bad += 1
            continue
        moved = ops = 0
        for x, y in zip(bodies_a, bodies_b):
            if x == y:
                continue
            moved += 1
            px, py = printed(x), printed(y)
            if px != py:
                ops += 1
                for line in list(difflib.unified_diff(
                        px.split("\n"), py.split("\n"), lineterm="",
                        n=1))[:40]:
                    print("    ", line)
        if ops:
            print("BODY OPERATIONS DIFFER", name, f"{ops} of {len(bodies_a)}")
            bad += 1
        else:
            print("equal but for locations", name,
                  f"({moved} of {len(bodies_a)} Mosaic bodies)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))

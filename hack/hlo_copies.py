"""Which arrays does a compiled program re-lay, and where? Lists, for each
optimized-HLO text given (``HLO_DIR=<dir> python hack/compile_cell.py <tree>
<config> decode`` writes them), the ``while`` loops and every ``copy`` /
``copy-start`` of at least ``MIN_MB`` (the environment's, 1 otherwise) with
its shape, layout and the computation it runs in:

    python hack/hlo_copies.py /root/scratch/hlo/olmo-hybrid-7b.decode.*.hlo.txt

A copy in the ``ENTRY`` computation runs once a call (once a chunk of decode
steps); one in any other computation runs wherever that computation is
called: in a layer scan's body or a ``cond``'s branch, once a layer. PR 47
found a 398 MB weight stack re-laid in every delta layer that way (11 ms a
step), at no chip time. Reads text only: no JAX."""
import os
import re
import sys

BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1, "s32": 4, "u32": 4,
         "pred": 1}
COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(")
COPY = re.compile(r"\s+(?:ROOT )?%([\w.\-]+) = \(?(\w+)\[([\d,]*)\](\{\S*\})? "
                  r"(copy|copy-start)\(")


def main(paths) -> int:
    floor = float(os.environ.get("MIN_MB", "1"))
    for path in paths:
        with open(path) as f:
            text = f.read()
        print(f"== {path}: {text.count(' while(')} while loops")
        where = "?"
        for line in text.split("\n"):
            head = COMPUTATION.match(line)
            if head:
                where = ("ENTRY " if head.group(1) else "") + head.group(2)
                continue
            m = COPY.match(line)
            if not m:
                continue
            name, dtype, dims, layout, kind = m.groups()
            size = BYTES.get(dtype, 4)
            for d in filter(None, dims.split(",")):
                size *= int(d)
            if size / 1e6 >= floor:
                print(f"  {where[:32]:32s} {name:16s} "
                      f"{dtype}[{dims}]{layout or '':36s} "
                      f"{size / 1e6:8.1f} MB {kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

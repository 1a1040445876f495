"""The optimised HLO of a cell's `jit_train_many` at the benchmark tests'
rehearsal size, on the CPU, with what changes from checkout to checkout
stripped -> sha256[:8]: how a PR that must leave every traced program alone
shows it, hash by hash against its parent (PR 30, PR 36, PR 43).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 tests/hlo_hash.py <root of a checkout> <out dir> <cell> ...

prints `<cell> <hash> <characters>` a cell and leaves the stripped text in
`<out dir>/<cell>.txt` for a diff. The sizes: a DeepFM cell at `vocabulary`
2^16, `batch_per_chip` 256, `steps_per_dispatch` 4; a language-model cell at
the `TINY` / `TINY_TRAFFIC` of its `benchmark/tests/test_<family>_cell.py`;
the cell's own `chips` (so four virtual devices for the mesh cell). A process
a cell: they share no trace cache that way.
Not collected: no test lives here (`tests/test_owner_compact.py` compares
two programs' texts by `strip`)."""

import hashlib
import importlib
import os
import re
import sys

DEEPFM_TINY = ({"vocabulary": 1 << 16},
               {"batch_per_chip": 256, "steps_per_dispatch": 4})
SEED = 2**31 + 77


def strip(text):
    """HLO text without `metadata={...}` (source lines, scope names) and
    without the four stack-frame tables at its head (FileNames, FunctionNames,
    FileLocations, StackFrames: paths and function names, which change with
    any rename or move)."""
    text = re.sub(r"(, )?metadata=\{[^}]*\}", "", text)
    keep, in_table = [], False
    for line in text.splitlines():
        if re.match(r"(FileNames|FunctionNames|FileLocations|StackFrames)\b",
                    line):
            in_table = True
        elif in_table and not re.match(r"\d+ ", line.strip()):
            in_table = False
        if not in_table:
            keep.append(line)
    return "\n".join(keep)


def scan_text(root, cell):
    """The compiled scan of `cell` as the checkout at `root` builds it."""
    sys.path.insert(0, root)
    os.chdir(root)
    from benchmark import run
    from openembedding_tpu.utils import compile_cache
    compile_cache.enable = lambda: None     # no cache directory of a tool's
    _, c, cfg, traffic = run.resolve(cell)
    if cfg["family"] == "deepfm":
        tiny, tiny_traffic = DEEPFM_TINY
    else:
        preset = importlib.import_module(
            f"benchmark.tests.test_{cfg['family']}_cell")
        tiny, tiny_traffic = preset.TINY, preset.TINY_TRAFFIC
    traffic = dict(traffic, **tiny_traffic)
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    session = driver.open_session(cfg=dict(cfg, **tiny), traffic=traffic,
                                  chips=c["chips"], seed=SEED)
    found = []

    class Done(Exception):
        pass

    def first_dispatch(self, mark):
        found.append(self.many.lower(self.state, self.stacked).compile()
                     .as_text())
        raise Done
    type(session)._first_dispatch = first_dispatch
    try:
        session.setup()
    except Done:
        pass
    return found[0]


if __name__ == "__main__":
    root, out = os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2])
    if len(sys.argv) > 4:       # a process a cell
        import subprocess
        sys.exit(max(subprocess.call([sys.executable, __file__, root, out, c])
                     for c in sys.argv[3:]))
    cell = sys.argv[3]
    text = strip(scan_text(root, cell))
    assert "metadata=" not in text and "FileNames" not in text
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, cell + ".txt"), "w") as f:
        f.write(text)
    print(cell, hashlib.sha256(text.encode()).hexdigest()[:8], len(text))

"""The control, at a size a test run holds: the plain reference computed
one precision below the configuration's, put in the program's place,
reads gaps several times wider than the program's own served tokens do
(so a limit between the two readings fails it).  On the chip at the
cell's own size the same readings come from ``bench/tools/control.py``."""
import numpy as np

from bench.common import serving
from bench.common.harness import load_json, load_module
from bench.tests.conftest import REPO, TINY_PHI3

PHI3_DIR = REPO / "bench/configs/phi3-mini-3.8b"


def _phi3_readings(seed: int) -> tuple[float, float]:
    from repro.serving import ServingEngine

    conf = load_json(PHI3_DIR / "config.json") | TINY_PHI3
    conf["serving"] |= {"slots": 2, "max_seq": 64, "reference_batch": 2}
    ref, system = load_module(PHI3_DIR / "reference.py"), load_module(PHI3_DIR / "system.py")
    w = ref.make_weights(conf, seed)
    eng = ServingEngine(system.program_config(conf), system.program_params(conf, w),
                        max_slots=2, max_seq=64)
    rng = np.random.default_rng(seed)
    for n in (8, 16, 24, 32):
        eng.submit(rng.integers(0, conf["vocab_size"], n, dtype=np.int32),
                   max_new_tokens=24)
    seqs = [(r.prompt, np.asarray(r.generated, np.int32))
            for r in sorted(eng.run(), key=lambda r: r.id)]
    program = serving.gap_numbers(ref.served_gaps(conf, w, seqs, margins=True))
    control = serving.gap_numbers(ref.served_gaps(conf, w, seqs, control=True,
                                                  margins=True))
    return program[serving.COMPARED], control[serving.COMPARED]


def test_phi3_control_reads_wider_than_the_program():
    readings = [_phi3_readings(s) for s in (1, 2, 3)]
    lower = max(p for p, _ in readings)
    upper = min(c for _, c in readings)
    assert upper >= 3 * lower, readings

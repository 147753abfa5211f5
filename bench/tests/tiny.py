"""The training cell cut to a size that a CPU test run can hold: the same
files, driver and checks, with small widths and short traffic."""
import time

from bench.lib import spec

TINY = {"hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512}
SEED = 2 ** 31 + 977


def train_cell():
    cell = spec.resolve(spec.load_benchmark(), "train.ds7b.s1024")
    cell.config = dict(cell.config, **TINY)
    cell.traffic = dict(cell.traffic, seq=64, global_batch=4, nodes=2,
                        docs=32)
    return cell


# Limits at this size, on the CPU (f32 matmuls are exact there): sound runs
# of the program read 7e-8 to 3e-6 on every number over three seeds; the
# control with float8 matmul inputs reads 1.1e-3 to 3.1e-3 (loss), 9.4e-3
# to 2.4e-2 (gradient) and 1.9e-3 to 2.4e-3 (change).  The cell's own
# limits, set from chip readings at the timed sizes, are in its traffic
# file.
LIMITS = {"loss_gap": 2e-5, "grad_gap": 2e-5, "delta_gap": 5e-5,
          "rows_off_corpus": 0}


def checks(readings):
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in readings.items()}


def now():
    return time.perf_counter()

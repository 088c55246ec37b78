"""The caller's time before the engine is ready: ``harness.T_PROCESS_START``
-> the close of the engine's last ``dstpu.setup.warmup`` span, less every
``dstpu.setup.*`` span in that stretch (the package's import among them) —
the backend's start, the benchmark's own files, the weights' draw from
``--seed`` (it sits between ``init_inference`` and ``set_params``), and in
training the reference's passes and the first-loss check."""
from benchmark import harness, setup_spans


def read(run):
    return setup_spans.outside_program_s(run, harness.T_PROCESS_START)

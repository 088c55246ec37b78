"""Distributed-aware logging.

TPU-native analog of the reference's ``deepspeed/utils/logging.py`` —
``log_dist`` logs only on selected ranks (here: JAX processes).
"""

import logging
import os
import sys
import functools

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


@functools.lru_cache(None)
def _create_logger(name="DeepSpeedTPU", level=logging.INFO):
    logger_ = logging.getLogger(name)
    logger_.setLevel(level)
    logger_.propagate = False
    if not logger_.handlers:
        # stderr: stdout belongs to the program's own result lines
        # (benchmark/run.py's and chip_smoke.py's last-line contracts,
        # `| tail -n 1` consumers).
        handler = logging.StreamHandler(stream=sys.stderr)
        handler.setFormatter(
            logging.Formatter("[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s"))
        logger_.addHandler(handler)
    return logger_


logger = _create_logger(
    level=LOG_LEVELS.get(os.environ.get("DSTPU_LOG_LEVEL", "info").lower(), logging.INFO))


def _process_index():
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


def log_dist(message, ranks=None, level=logging.INFO):
    """Log ``message`` only on the given process ranks (None/[-1 in list] = all)."""
    my_rank = _process_index()
    if ranks is None or my_rank in ranks or -1 in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def print_rank_0(message):
    if _process_index() == 0:
        print(message, flush=True)


def warning_once(message, _seen=set()):
    if message not in _seen:
        _seen.add(message)
        logger.warning(message)

"""Framework logger.

Analog of reference ``autodist/utils/logging.py``: a dedicated
``logging.Logger('autodist_tpu')`` writing to stderr and a per-run file under
``DEFAULT_LOG_DIR``, level controlled by ``AUTODIST_MIN_LOG_LEVEL``.
"""
import datetime
import logging as _logging
import os
import sys
import threading

from autodist_tpu.const import DEFAULT_LOG_DIR, ENV

_logger = None
_logger_lock = threading.Lock()

_FMT = "%(asctime)s %(levelname)s [pid %(process)d] %(name)s: %(message)s"


def _create_logger():
    logger = _logging.getLogger("autodist_tpu")
    logger.propagate = False
    level = ENV.AUTODIST_MIN_LOG_LEVEL.val.upper()
    logger.setLevel(getattr(_logging, level, _logging.INFO))
    stream = _logging.StreamHandler(sys.stderr)
    stream.setFormatter(_logging.Formatter(_FMT))
    logger.addHandler(stream)
    try:
        os.makedirs(DEFAULT_LOG_DIR, exist_ok=True)
        ts = datetime.datetime.now(datetime.timezone.utc).strftime("%Y%m%d-%H%M%S")
        fh = _logging.FileHandler(os.path.join(DEFAULT_LOG_DIR, f"{ts}-{os.getpid()}.log"))
        fh.setFormatter(_logging.Formatter(_FMT))
        logger.addHandler(fh)
    except OSError:  # read-only fs etc.
        pass
    return logger


def get_logger():
    global _logger
    if _logger is None:
        with _logger_lock:
            if _logger is None:
                _logger = _create_logger()
    return _logger


def debug(msg, *args, **kwargs):
    get_logger().debug(msg, *args, **kwargs)


def info(msg, *args, **kwargs):
    get_logger().info(msg, *args, **kwargs)


def warning(msg, *args, **kwargs):
    get_logger().warning(msg, *args, **kwargs)


_said = set()


def _once(say, msg, args):
    if (msg, args) not in _said:
        _said.add((msg, args))
        say(msg, *args)


def warning_once(msg, *args):
    """``warning`` that drops exact repeats: for notes made at trace time,
    which every re-trace of the same site would otherwise print again.
    ``args`` must be hashable (shapes, dtypes, names)."""
    _once(warning, msg, args)


def info_once(msg, *args):
    """``info`` under the rule of ``warning_once``."""
    _once(info, msg, args)


def error(msg, *args, **kwargs):
    get_logger().error(msg, *args, **kwargs)


def set_verbosity(level):
    get_logger().setLevel(level)

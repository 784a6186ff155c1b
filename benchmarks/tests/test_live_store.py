"""harness.workdir_bytes on a store that changes under it: what the
harness reads between the window's end and its result has to come back
whatever the program's compaction threads do to the files meanwhile.
The expression it replaced (PR 35's parent, harness.py:350) is kept
here as the thing guarded against. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests/test_live_store.py -q -p no:cacheprovider
"""

import contextlib
import os
import shutil
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import workdir_bytes  # noqa: E402


def parents_expression(workdir):
    """benchmarks/harness.py:350-351 up to PR 34, letter for letter."""
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(workdir) for f in files)


def _tree(root):
    """A replica's directory in small: sst/ with three tables, a log
    beside it. Returns {path: bytes}."""
    sizes = {"app/sst/l1-1.sst": 4096, "app/sst/l1-2.sst": 1000,
             "app/sst/l0-3.sst": 10, "plog/log.1": 300, "info": 7}
    for rel, n in sizes.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(b"x" * n)
    return {os.path.join(root, rel): n for rel, n in sizes.items()}


# what a publish does to a name between its listing and its stat
STRIKES = {
    "unlinked": lambda path: os.unlink(path),
    "renamed": lambda path: os.replace(path, path + ".old"),
    "directory_removed": lambda path: shutil.rmtree(os.path.dirname(path)),
}


@pytest.mark.parametrize("strike", sorted(STRIKES))
def test_a_name_that_vanishes_between_listing_and_stat(strike, tmp_path,
                                                        monkeypatch):
    victim = "l1-2.sst"
    real_getsize, real_scandir = os.path.getsize, os.scandir

    # the parent's expression: the name is listed, struck, then sized
    root = str(tmp_path / "old")
    _tree(root)

    def getsize(path):
        if os.path.basename(path) == victim:
            STRIKES[strike](path)
        return real_getsize(path)

    with monkeypatch.context() as m:
        m.setattr(os.path, "getsize", getsize)
        with pytest.raises(FileNotFoundError):
            parents_expression(root)

    # the harness's code: the entry is listed, struck, then handed over
    root = str(tmp_path / "new")
    sizes = _tree(root)
    struck = []

    @contextlib.contextmanager
    def scandir(path):
        def entries(listing):
            for entry in listing:
                if entry.name == victim and not struck:
                    struck.append(entry.path)   # rmtree lists it again
                    STRIKES[strike](entry.path)
                yield entry

        with real_scandir(path) as listing:
            yield entries(listing)

    with monkeypatch.context() as m:
        m.setattr(os, "scandir", scandir)
        got = workdir_bytes(root)
    assert struck == [os.path.join(root, "app/sst", victim)]
    assert not os.path.exists(struck[0])
    # a number: the bytes of the files that remained. A renamed file
    # counts under its new name only if the listing came to hold it; of
    # a removed directory, the files that were sized before it went
    remained = sum(n for path, n in sizes.items() if os.path.exists(path))
    gone = sizes[struck[0]]
    if strike == "unlinked":
        assert got == remained
    elif strike == "renamed":
        assert got in (remained, remained + gone)
    else:
        assert remained <= got <= sum(sizes.values()) - gone
    assert workdir_bytes(root) == parents_expression(root) \
        == remained + (gone if strike == "renamed" else 0)


def test_a_directory_that_vanishes_before_it_is_listed(tmp_path,
                                                       monkeypatch):
    root = str(tmp_path)
    sizes = _tree(root)
    real_scandir = os.scandir

    def scandir(path):
        if isinstance(path, str) and path.endswith("sst"):
            shutil.rmtree(path)     # listed by its parent, gone since
        return real_scandir(path)

    monkeypatch.setattr(os, "scandir", scandir)
    assert workdir_bytes(root) == sum(
        n for path, n in sizes.items() if "sst" not in path)
    assert workdir_bytes(os.path.join(root, "nowhere")) == 0


def test_a_quiet_tree_reads_as_the_parents_expression_did(tmp_path):
    sizes = _tree(str(tmp_path))
    assert workdir_bytes(str(tmp_path)) == parents_expression(
        str(tmp_path)) == sum(sizes.values())


def test_publishes_on_a_thread_never_raise(tmp_path):
    """A thread rewrites the tables of a few replicas the way a publish
    does (write x.sst.tmp, rename it onto a new name, unlink an older
    table; now and then a whole replica's directory goes and comes
    back) while the harness's function walks the tree 1,000 times and
    more. The parent's expression, called beside it, is only counted:
    whether it meets the race here is luck."""
    root = str(tmp_path)
    dirs = [os.path.join(root, f"node0/1.{p}/app/sst") for p in range(8)]
    for d in dirs:
        os.makedirs(d)
    stop = threading.Event()
    failed = []

    def publisher():
        try:
            seq = 0
            while not stop.is_set():
                seq += 1
                d = dirs[seq % len(dirs)]
                os.makedirs(d, exist_ok=True)
                tmp = os.path.join(d, f"l1-{seq}.sst.tmp")
                with open(tmp, "wb") as f:
                    f.write(b"x" * (1 + seq % 4096))
                os.replace(tmp, os.path.join(d, f"l1-{seq}.sst"))
                old = os.path.join(d, f"l1-{seq - len(dirs)}.sst")
                if os.path.exists(old):
                    os.unlink(old)
                if seq % 97 == 0:
                    shutil.rmtree(os.path.dirname(d))
        except BaseException as exc:  # noqa: BLE001 - told to the test
            failed.append(exc)

    thread = threading.Thread(target=publisher, name="publisher")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    calls = old_raised = 0
    try:
        thread.start()
        t_end = time.perf_counter() + 1.5
        while calls < 1000 or time.perf_counter() < t_end:
            assert workdir_bytes(root) >= 0
            calls += 1
            try:
                parents_expression(root)
            except OSError:
                old_raised += 1
    finally:
        stop.set()
        thread.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not thread.is_alive() and not failed, failed
    assert calls >= 1000
    print(f"{calls} calls of workdir_bytes, none raised; the parent's "
          f"expression raised in {old_raised} of {calls}")

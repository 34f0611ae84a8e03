"""A case that passes, then one that is still out when the run is cut."""
import time


def test_ends_before_the_cut():
    pass


def test_is_out_when_the_run_is_cut(tmp_path_factory):
    (tmp_path_factory.getbasetemp() / "out").touch()
    time.sleep(2.5)

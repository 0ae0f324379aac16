import pytest

from . import trees


@pytest.fixture(scope="session")
def roots(tmp_path_factory):
    """Where each of ``trees.TREES`` lies: the repository, and the copy with
    the next PR's files added (made once a session)."""
    return {"real": trees.ROOT, "next": trees.make_next(tmp_path_factory.mktemp("next_tree"))}

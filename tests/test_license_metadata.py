"""Declared license metadata must match the committed LICENSE text.

The declared license once flipped Apache-2.0/MIT across rounds; this pins
the two sources of truth together so a future edit to either one fails
loudly instead of shipping contradictory licensing."""

import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: canonical first-line fingerprints of the license texts we could ship
_FINGERPRINTS = {
    "Apache-2.0": "Apache License",
    "MIT": "MIT License",
    "BSD-3-Clause": "BSD 3-Clause License",
}


def _declared_license() -> str:
    import tomllib

    with open(os.path.join(REPO, "pyproject.toml"), "rb") as fh:
        license_field = tomllib.load(fh)["project"]["license"]
    if isinstance(license_field, dict):
        return license_field["text"]
    return license_field


def test_pyproject_license_matches_license_file():
    declared = _declared_license()
    assert declared in _FINGERPRINTS, (
        f"unrecognized declared license {declared!r} — extend the "
        f"fingerprint table if this is intentional"
    )
    with open(os.path.join(REPO, "LICENSE")) as fh:
        head = fh.read(2048)
    assert _FINGERPRINTS[declared] in head, (
        f"pyproject.toml declares {declared} but LICENSE does not open "
        f"with {_FINGERPRINTS[declared]!r}"
    )
    # and no OTHER known license text is what's actually committed
    for spdx, fingerprint in _FINGERPRINTS.items():
        if spdx != declared:
            assert fingerprint not in head, (
                f"LICENSE looks like {spdx} but pyproject declares {declared}"
            )

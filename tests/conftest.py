"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
multi-device behaviour is tested via subprocesses (test_distributed.py)."""
from __future__ import annotations

import pytest

from repro.data.datasets import (
    make_credit_card,
    make_expedia,
    make_flights,
    make_hospital,
)
from repro.ml import (
    DecisionTreeClassifier,
    GradientBoostingClassifier,
    LogisticRegression,
    RandomForestClassifier,
    fit_pipeline,
)


@pytest.fixture(scope="session")
def hospital():
    return make_hospital(2048, seed=1)


@pytest.fixture(scope="session")
def credit_card():
    return make_credit_card(1024, seed=0)


@pytest.fixture(scope="session")
def expedia():
    return make_expedia(1024, seed=2)


@pytest.fixture(scope="session")
def flights():
    return make_flights(1024, seed=3)


ESTIMATORS = {
    "dt": lambda: DecisionTreeClassifier(max_depth=6),
    "lr": lambda: LogisticRegression(alpha=0.003, n_iter=120),
    "gb": lambda: GradientBoostingClassifier(n_estimators=8, max_depth=3),
    "rf": lambda: RandomForestClassifier(n_estimators=6, max_depth=5),
}


def train_pipeline(ds, kind: str):
    joined = ds.joined_columns()
    return fit_pipeline(
        joined, ds.label, ds.numeric, ds.categorical,
        ESTIMATORS[kind](), categories=ds.categories(),
    )


@pytest.fixture(scope="session")
def hospital_dt(hospital):
    return train_pipeline(hospital, "dt")


@pytest.fixture(scope="session")
def hospital_gb(hospital):
    return train_pipeline(hospital, "gb")


@pytest.fixture(scope="session")
def hospital_lr(hospital):
    return train_pipeline(hospital, "lr")

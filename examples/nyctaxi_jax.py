"""NYCTaxi fare regression, end to end — the reference's flagship example
(examples/pytorch_nyctaxi.py) reshaped: ETL feature engineering on the
distributed DataFrame engine, exchange into the object store, JaxEstimator MLP
trained data-parallel on the device mesh.

Uses synthetic taxi-shaped data by default; pass a parquet directory of real
NYCTaxi data as argv[1] to run on it.
"""
# raydp-lint: disable-file=print-diagnostics  (examples narrate to stdout by design — they run standalone, before any obs plane exists)

import os
import sys

import numpy as np
import pandas as pd

import raydp_tpu
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.etl import functions as F
from raydp_tpu.models import MLPRegressor


def synthetic_taxi(n_rows: int) -> pd.DataFrame:
    rng = np.random.default_rng(7)
    base = pd.Timestamp("2020-01-01").value // 10**9
    duration = rng.integers(120, 3600, n_rows)
    return pd.DataFrame(
        {
            "pickup_ts": pd.to_datetime(
                base + rng.integers(0, 30 * 24 * 3600, n_rows), unit="s"
            ),
            "passenger_count": rng.integers(1, 6, n_rows).astype(np.int64),
            "pickup_longitude": -74.0 + rng.random(n_rows) * 0.1,
            "pickup_latitude": 40.7 + rng.random(n_rows) * 0.1,
            "dropoff_longitude": -74.0 + rng.random(n_rows) * 0.1,
            "dropoff_latitude": 40.7 + rng.random(n_rows) * 0.1,
            "fare_amount": 2.5 + duration / 240.0 + rng.random(n_rows),
        }
    )


def main():
    session = raydp_tpu.init_etl(
        "nyctaxi", num_executors=2, executor_cores=2, executor_memory="1G"
    )
    if len(sys.argv) > 1:
        df = session.read_parquet(sys.argv[1])
    else:
        rows = int(os.environ.get("EXAMPLE_ROWS", 100_000))
        df = session.from_pandas(synthetic_taxi(rows), num_partitions=8)

    df = (
        df.with_column("hour", F.hour("pickup_ts").cast("float32"))
        .with_column("dow", F.dayofweek("pickup_ts").cast("float32"))
        .with_column("dx", F.col("dropoff_longitude") - F.col("pickup_longitude"))
        .with_column("dy", F.col("dropoff_latitude") - F.col("pickup_latitude"))
        .with_column(
            "dist",
            F.sqrt(F.col("dx") * F.col("dx") + F.col("dy") * F.col("dy")).cast("float32"),
        )
        .with_column("pc", F.col("passenger_count").cast("float32"))
        .with_column("label", F.col("fare_amount").cast("float32"))
        .select("hour", "dow", "dist", "pc", "label")
        .dropna()
    )
    train_df, test_df = df.random_split([0.9, 0.1], seed=0)

    est = JaxEstimator(
        model=MLPRegressor(),
        optimizer="adam",
        loss="mse",
        metrics=["mse", "mae"],
        feature_columns=["hour", "dow", "dist", "pc"],
        label_column="label",
        batch_size=256,
        num_epochs=int(os.environ.get("EXAMPLE_EPOCHS", 5)),
        learning_rate=1e-3,
        # for datasets larger than host memory, pass streaming=True
        # (O(block) memory)
    )
    history = est.fit_on_etl(train_df, test_df, stop_etl_after_conversion=True)
    for record in history:
        print(record)


if __name__ == "__main__":
    main()

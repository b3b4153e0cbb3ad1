"""Every BASELINE.json config through the public API, device vs host
differential."""

from csvplus_tpu import Like, SetValue, Take, from_file


def test_config1_filter_map(people_csv, tmp_path):
    def filter_map(source):
        return source.filter(Like({"name": "Amelia"})).map(SetValue("name", "Julia"))

    host = filter_map(Take(from_file(people_csv)))
    dev = filter_map(from_file(people_csv).on_device("cpu"))
    a, b = str(tmp_path / "h.csv"), str(tmp_path / "d.csv")
    host.to_csv_file(a, "name", "surname")
    dev.to_csv_file(b, "name", "surname")
    assert open(b, "rb").read() == open(a, "rb").read()


def test_config2_index_build(people_csv):
    probes = [("5",), ("119",), ("nope",)]
    hi = Take(from_file(people_csv)).unique_index_on("id")
    di = from_file(people_csv).on_device("cpu").unique_index_on("id")
    hr = [hi.find(*p).to_rows() for p in probes]
    dr = [di.find(*p).to_rows() for p in probes]
    assert dr == hr and len(di) == len(hi) == 120


def test_config3_threeway(people_csv, stock_csv, orders_csv):
    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    prod = Take(
        from_file(stock_csv).select_columns("prod_id", "product", "price")
    ).unique_index_on("prod_id")
    host = (
        Take(from_file(orders_csv).select_columns("cust_id", "prod_id", "qty"))
        .join(cust, "cust_id")
        .join(prod, "prod_id")
        .to_rows()
    )
    cust.on_device("cpu")
    prod.on_device("cpu")
    dev = (
        from_file(orders_csv)
        .on_device("cpu")
        .select_columns("cust_id", "prod_id", "qty")
        .join(cust, "cust_id")
        .join(prod, "prod_id")
        .to_rows()
    )
    assert dev == host


def test_config4_dedup(people_csv):
    hi = Take(from_file(people_csv)).index_on("name")
    di = from_file(people_csv).on_device("cpu").index_on("name")
    hi.resolve_duplicates("first")
    di.resolve_duplicates("first")
    assert Take(di).to_rows() == Take(hi).to_rows()
    assert len(di) == 10


def test_config5_sharded_join(people_csv, orders_csv):
    cust = Take(
        from_file(people_csv).select_columns("id", "name")
    ).unique_index_on("id")
    host = (
        Take(from_file(orders_csv))
        .join(cust, "cust_id")
        .to_rows()
    )
    cust.on_device("cpu")
    dev = from_file(orders_csv).on_device(shards=8).join(cust, "cust_id").to_rows()
    assert dev == host

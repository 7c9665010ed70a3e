"""SQL tables to property graph via Graph DDL.

The TPU-native analog of the reference's ``CensusJdbcExample`` /
``CypherSQLRoundtripExample``: existing relational tables (an HR schema
here — in production, parquet/CSV exports or any host-side provider) are
mapped onto a property graph by the reference's Graph DDL language
(``GraphDdlParser.scala:66``), then queried with Cypher. Both of the
reference's id-generation strategies work; HASHED_ID is used here.

A CPU tool by purpose (a toy-sized walkthrough of the API): it defaults
``JAX_PLATFORMS`` to ``cpu``; set the variable to run it elsewhere.

Run:  python examples/11_sql_graphddl.py
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

DDL = """
SET SCHEMA hr.db

CREATE GRAPH TYPE orgType (
  Employee (name STRING, salary INTEGER),
  Dept (title STRING),
  WORKS_IN,

  (Employee),
  (Dept),
  (Employee)-[WORKS_IN]->(Dept)
)

CREATE GRAPH org OF orgType (
  (Employee) FROM employees,
  (Dept) FROM departments,
  (Employee)-[WORKS_IN]->(Dept)
    FROM assignments edge
      START NODES (Employee) FROM employees emp
        JOIN ON emp.id = edge.emp_id
      END NODES (Dept) FROM departments dep
        JOIN ON dep.id = edge.dept_id
)
"""

TABLES = {
    "db.employees": {
        "id": [1, 2, 3],
        "name": ["Ada", "Bob", "Cyd"],
        "salary": [120, 90, 150],
    },
    "db.departments": {"id": [10, 20], "title": ["TPU", "Compilers"]},
    "db.assignments": {
        "emp_id": [1, 2, 3],
        "dept_id": [10, 10, 20],
    },
}


def main():
    from tpu_cypher import CypherSession
    from tpu_cypher.io.sql import (
        InMemoryTables,
        SqlPropertyGraphDataSource,
    )

    session = CypherSession.tpu()
    session.register_source(
        "sql", SqlPropertyGraphDataSource(DDL, {"hr": InMemoryTables(TABLES)})
    )
    g = session.graph("sql.org")
    out = [
        dict(r)
        for r in g.cypher(
            """
            MATCH (e:Employee)-[:WORKS_IN]->(d:Dept)
            RETURN d.title AS dept, count(e) AS heads, max(e.salary) AS top
            ORDER BY dept
            """
        ).records.collect()
    ]
    for row in out:
        print(f"sql-ddl {row['dept']}: heads={row['heads']} top={row['top']}")
    assert out == [
        {"dept": "Compilers", "heads": 1, "top": 150},
        {"dept": "TPU", "heads": 2, "top": 120},
    ]
    print("departments:", len(out))


if __name__ == "__main__":
    main()

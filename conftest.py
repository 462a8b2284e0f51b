"""Collection for the whole repository: pytest's default directory
rules, plus two git-ignored directories that may hold copies of this
tree, whose test files share basenames with the real ones and would
break collection ("import file mismatch")."""
collect_ignore_glob = ["archive_check", "chiprun_out"]

# Repo tooling namespace (slatelint lives here; c_api is a plain
# script directory).
